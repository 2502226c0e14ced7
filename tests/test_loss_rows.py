"""The loss rows: each logits row's cross-entropy, computed in the step
program beside the loss gradient on every route of ``run_pallas`` (the
fused walk, ``fuse=False``, the ``shard_map`` mesh step) and on the host
by ``run_reference``; ``train_graph``'s loss is their float64 mean.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.lower import (
    NetworkGraph,
    PlanCache,
    lm_token_batches,
    lower_training_step,
    paper_cnn_graph,
    run_pallas,
    run_reference,
    train_graph,
)
from repro.lower import executors

ROOT = Path(__file__).resolve().parents[1]


def host_rows(logits, onehot):
    """float64 cross-entropy of each row, from the step's own logits."""
    z = np.asarray(logits, np.float64).reshape(np.shape(onehot))
    m = z.max(axis=1, keepdims=True)
    lse = m[:, 0] + np.log(np.exp(z - m).sum(axis=1))
    return lse - z[np.arange(len(z)), np.argmax(onehot, axis=1)]


def _tiny_lm(batch=2, seq=6, n_layers=2):
    from repro.models.config import ModelConfig

    cfg = ModelConfig(
        name="tiny", family="dense", n_layers=n_layers, d_model=16, n_heads=2,
        n_kv_heads=2, head_dim=8, d_ff=32, vocab_size=13,
    )
    return NetworkGraph.from_model_config(cfg, batch=batch, seq=seq, lr=0.05)


def _batches(graph, seed=0):
    """``batch_fn`` of (inputs, label ids) for a CNN or a token-row graph."""
    rng = np.random.RandomState(seed)
    if len(graph.input_shape) == 2:  # token rows: (seq, vocab)
        seq, vocab = graph.input_shape
        return lm_token_batches(rng, graph.batch, seq, vocab)

    def batch_fn(_i):
        x = rng.randn(graph.batch, *graph.input_shape).astype(np.float32)
        return x, rng.randint(0, graph.loss.classes, graph.batch)

    return batch_fn


def _inputs(graph, seed=0):
    x, labels = _batches(graph, seed)(0)
    onehot = np.eye(graph.loss.classes, dtype=np.float32)[labels]
    return {graph.input_edge: x, graph.label_edge: onehot,
            **graph.init_params(seed=seed + 1)}


GRAPHS = {
    # batch 16 splits the region kernel into two batch tiles
    "cnn": lambda: paper_cnn_graph(batch=16, img=8),
    "lm": _tiny_lm,
}


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "per_node"])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_rows_match_float64_rows_of_the_steps_logits(name, fuse):
    graph = GRAPHS[name]()
    prog = lower_training_step(graph)
    inputs = _inputs(graph)
    outs = run_pallas(prog, inputs, cache=PlanCache(), fuse=fuse)
    rows = np.asarray(outs[graph.loss_rows_edge])
    assert rows.dtype == np.float32 and rows.shape == (graph.loss.batch,)
    np.testing.assert_allclose(
        rows, host_rows(outs[graph.logits_edge], inputs[graph.label_edge]),
        rtol=1e-6)
    if fuse:
        # the CNN's loss stage runs inside a region kernel, the LM's as the
        # per-node plan of the fused walk
        fused = executors._fusion_for(prog, fuse_updates=True).fused_steps
        assert ("loss:dx" in fused) == (name == "cnn")


@pytest.mark.parametrize("backend", ["pallas", "reference"])
def test_train_graph_loss_is_the_float64_mean_of_the_rows(backend):
    graph = paper_cnn_graph(batch=2, img=8)
    prog = lower_training_step(graph)
    params = graph.init_params(seed=1)
    cache = PlanCache()
    res = train_graph(graph, 1, _batches(graph), backend=backend,
                      program=prog, params=params, cache=cache)
    x, labels = _batches(graph)(0)
    inputs = {graph.input_edge: x,
              graph.label_edge: np.eye(10, dtype=np.float32)[labels], **params}
    if backend == "reference":
        outs = run_reference(prog, inputs)
    else:
        outs = run_pallas(prog, inputs, cache=cache)
    rows = np.asarray(outs[graph.loss_rows_edge])
    assert res["losses"] == [float(np.mean(np.asarray(rows, np.float64)))]
    want = host_rows(outs[graph.logits_edge], inputs[graph.label_edge])
    np.testing.assert_allclose(rows, want, rtol=1e-6)


def test_shard_map_rows_concatenate_in_batch_order():
    """On four (fake CPU) devices a 2x2 mesh program runs under
    ``shard_map``: the shards' rows come back in batch order, equal to
    the rows of the single-device step and to float64 rows of the mesh
    step's own logits: the CNN's from a region kernel, the LM's from the
    loss plan of the fused walk."""
    script = (
        "import jax, numpy as np\n"
        "from repro.lower import PlanCache, lower_training_step, run_pallas,"
        " shard_training_step, paper_cnn_graph\n"
        "from test_loss_rows import _tiny_lm, _inputs, host_rows\n"
        "assert jax.device_count() == 4\n"
        "for graph in (paper_cnn_graph(batch=4, img=8),"
        " _tiny_lm(batch=4, seq=4, n_layers=1)):\n"
        "    prog = lower_training_step(graph)\n"
        "    mesh = shard_training_step(graph, mesh_shape=(2, 2), program=prog).program\n"
        "    inputs = _inputs(graph)\n"
        "    one = np.asarray(run_pallas(prog, inputs, cache=PlanCache())"
        "[graph.loss_rows_edge])\n"
        "    outs = run_pallas(mesh, inputs, cache=PlanCache())\n"
        "    rows = np.asarray(outs[graph.loss_rows_edge])\n"
        "    assert rows.shape == (graph.loss.batch,), rows.shape\n"
        "    np.testing.assert_allclose(rows, one, rtol=1e-6)\n"
        "    np.testing.assert_allclose(rows, host_rows(outs[graph.logits_edge],"
        " inputs[graph.label_edge]), rtol=1e-6)\n"
        "print('ok')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT / "tests")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr[-3000:]
