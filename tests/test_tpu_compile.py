"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached.

The TPU compiler ships with libtpu and compiles for a described
``v5e:2x2`` topology on the CPU, so what Mosaic or XLA would refuse on the
chip (tile alignment, gathers in a kernel body, scoped VMEM) fails here,
with ``interpret=False``. Nothing runs: these tests say nothing about
results or times.

libtpu admits one process at a time and keeps its lock until exit, so the
topology is described inside a module fixture, never at import, and every
case that needs it lives in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import streaming
from repro.lower import (
    BatchedSpec,
    Conv2dSpec,
    NetworkGraph,
    PlanCache,
    lower_training_step,
    paper_cnn_graph,
)
from repro.lower import executors


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # the compiler otherwise writes its logs outside the checkout
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or another process holds it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip cannot be read back without one:
    keep the persistent cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for(one_chip, no_compile_cache):
    """``compile_for(fn, *shapes)``: jit ``fn`` and compile it for one v5e
    chip; returns the compiled executable."""

    def sds(shape):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.float32, sharding=one_chip)

    def compile_(fn, *args):
        def is_shape(a):
            return isinstance(a, tuple) and all(isinstance(d, int) for d in a)

        args = jax.tree.map(sds, args, is_leaf=is_shape)
        return jax.jit(fn).lower(*args).compile()

    return compile_


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _step_shapes(graph) -> dict:
    """Shapes of a train step's inputs: the input edge, labels, params."""
    if isinstance(graph.nodes[0].spec, Conv2dSpec):
        x = (graph.batch,) + tuple(graph.input_shape)
    else:  # token rows: (B*S, V)
        x = (graph.batch * graph.input_shape[0], graph.input_shape[1])
    shapes = {graph.input_edge: x, graph.label_edge: (graph.loss.batch, graph.loss.classes)}
    for p, shp in graph.param_shapes().items():
        shapes[p] = tuple(shp)
        if graph.momentum:
            shapes[f"v_{p}"] = tuple(shp)
    return shapes


def _fused_step(graph):
    program = lower_training_step(graph)
    fusion = executors._fusion_for(program, fuse_updates=True)
    plan = executors._step_plan(
        PlanCache(), graph, fusion, program.design.name, False, keep_grads=True
    )
    return plan.fn


@pytest.mark.parametrize(
    "m,k,n",
    [(8, 32, 10), (8, 32, 16), (8, 32, 64), (128, 1024, 2816)],
    ids=["n10", "n16", "n64", "qwen_fc1"],
)
def test_streaming_matmul_compiles(compile_for, m, k, n):
    compiled = compile_for(
        lambda a, b: streaming.streaming_matmul(a, b, interpret=False), (m, k), (k, n)
    )
    assert _kernels(compiled) == 1


def test_paper_cnn_fused_step_compiles(compile_for):
    # the `--backend ntx` CLI defaults: batch 8, 16x16 images, momentum 0.9
    graph = paper_cnn_graph(batch=8, img=16, lr=0.05, momentum=0.9)
    compiled = compile_for(_fused_step(graph), _step_shapes(graph))
    assert _kernels(compiled) == 1  # the whole step is one region kernel


@pytest.mark.parametrize("pass_", ["fwd", "dw", "dx"])
@pytest.mark.parametrize("node", ["c1", "c2"])
def test_batched_conv_plan_compiles(compile_for, node, pass_):
    """The per-node (`--no-fuse`) conv plans over the CLI-default batch."""
    graph = paper_cnn_graph(batch=8, img=16, momentum=0.9)
    spec = next(n.spec for n in graph.nodes if n.name == node)
    x = (8, spec.in_h, spec.in_w, spec.cin)
    w = (spec.kh, spec.kw, spec.cin, spec.cout)
    dy = (8, spec.out_h, spec.out_w, spec.cout)
    ins = {"fwd": {"x": x, "w": w}, "dw": {"x": x, "dy": dy}, "dx": {"dy": dy, "w": w}}[pass_]
    plan = executors._plan_callable(BatchedSpec(spec, 8), pass_, False)
    compiled = compile_for(plan, ins)
    assert _kernels(compiled) >= 1


def test_qwen_width_lm_step_compiles(compile_for):
    """One layer of qwen1.5-0.5B at its published widths, full vocabulary."""
    cfg = get_config("qwen1_5_0_5b").with_(n_layers=1)
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size) == (1024, 2816, 151936)
    graph = NetworkGraph.from_model_config(cfg, batch=4, seq=128, lr=0.05)
    compiled = compile_for(_fused_step(graph), _step_shapes(graph))
    # every matmul pass of the step is a streaming kernel
    assert _kernels(compiled) == 17
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < 16e9
