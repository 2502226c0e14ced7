"""Compile each cell's train step at its timed sizes for a described TPU
v5e, on the CPU, and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu python chipbench/rehearse.py [--workload <cell> ...]

Nothing runs: this finds what the chip's compiler refuses, and how much
device memory the step program needs, before any chip time is spent. It
compiles the program's one jitted step callable the way ``run_pallas``
builds it on a TPU (compiled kernels, no interpreter).
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def rehearse(workload: str, one_chip) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench.catalog import Catalog
    from repro.lower import PlanCache, lower_training_step
    from repro.lower import executors

    cat = Catalog()
    cell = cat.cell(workload)
    cfg, mix = cat.config(cell["config"]), cat.traffic(cell["traffic"])
    graph = cat.family(cfg["family"]).build(cfg, mix)
    program = lower_training_step(graph)
    fusion = executors._fusion_for(program, fuse_updates=True)
    step = executors._step_plan(PlanCache(), graph, fusion, program.design.name,
                                False, keep_grads=program.meta.get("keep_grads", True))
    first = graph.nodes[0]
    x_shape = (graph.loss.batch,) + tuple(first.in_shape[1:]) \
        if len(first.in_shape) == 2 else (graph.batch,) + tuple(first.in_shape)
    shapes = {graph.input_edge: x_shape,
              graph.label_edge: (graph.loss.batch, graph.loss.classes)}
    for name, shape in graph.param_shapes().items():
        shapes[name] = shape
        if graph.momentum:
            shapes[f"v_{name}"] = shape
    args = {k: jax.ShapeDtypeStruct(v, jnp.float32, sharding=one_chip)
            for k, v in shapes.items()}
    t = time.perf_counter()
    compiled = step.fn.lower(args).compile()
    mem = compiled.memory_analysis()
    return {
        "workload": workload, "compile_s": round(time.perf_counter() - t, 1),
        "regions": fusion.n_regions, "per_node_steps": len(fusion.fallback_steps),
        "kernels": compiled.as_text().count("tpu_custom_call"),
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench.catalog import Catalog

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for w in args.workload or [c["name"] for c in Catalog().bench["workloads"]]:
        print(rehearse(w, one_chip), flush=True)


if __name__ == "__main__":
    main()
