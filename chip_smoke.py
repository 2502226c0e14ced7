"""Smoke test of the NTX train step on a TPU, through the normal entry points.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: the 2x2 data-parallel LM step

Phase A trains ``qwen1_5_0_5b`` at its published widths (24 layers, d=1024,
d_ff=2816, vocab 151936) for three steps of batch 4 x seq 128 with
``run_ntx_lm`` and checks every gradient of one step against the fp32
``jax.grad`` oracle. Phase B trains the paper CNN at the CLI defaults with
the region fuser and again per node, and compares the two loss curves.
``--chips 4`` instead runs one phase-A step as a 2x2 mesh program under
``shard_map`` and compares its updated params with the same step on one
chip.

Every kernel is compiled (``interpret=False``): off a TPU the script exits
non-zero before any phase runs. The last line of stdout is a JSON object
naming the device; it is printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
LM_ARCH = "qwen1_5_0_5b"
LM_BATCH, LM_SEQ, LM_STEPS = 4, 128, 3
CNN_BATCH, CNN_IMG, CNN_STEPS = 8, 16, 3  # the launch/train.py CLI defaults
RTOL, ATOL = 1e-4, 1e-5  # fp32 tolerance, as in run_ntx_lm's grad check


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self.secs = dict.fromkeys(self.EVENTS, 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.secs:
            self.secs[event] += duration

    def snapshot(self) -> dict:
        return {e.rsplit("/", 1)[-1]: round(s, 3) for e, s in self.secs.items()}


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip smoke FAILED: {what}")


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_lm(train, interpret: bool) -> None:
    """Phase A: the decoder LM at full width through ``run_ntx_lm``."""
    t0 = time.perf_counter()
    res = train.run_ntx_lm(
        LM_ARCH, LM_STEPS, LM_BATCH, LM_SEQ, reduced=False,
        interpret=interpret, check_grads=True,
    )
    losses, walls = res["losses"], res["walls"]
    print(f"phase A losses: {losses}")
    print(f"phase A step walls (s): {walls}")
    print(f"phase A worst grad rel err: {res['grad_check']['worst_rel_err']:.3e} "
          f"over {res['grad_check']['n_params']} params; "
          f"{res['grad_check']['relu_flips']} ReLU elements on the other side of the kink")
    print(f"phase A peak_bytes_in_use: {peak_bytes()}")
    print(f"phase A wall incl. set-up: {time.perf_counter() - t0:.1f} s")
    check(len(losses) == LM_STEPS, f"{len(losses)} LM losses")
    check(all(math.isfinite(v) for v in losses), f"non-finite LM loss {losses}")
    check(all(b < a for a, b in zip(losses, losses[1:])),
          f"LM loss did not decrease every step {losses}")


def phase_cnn(train, interpret: bool) -> None:
    """Phase B: the paper CNN, fused regions vs per-node plans."""
    runs = {}
    for fuse in (True, False):
        res = train.run_ntx_cnn(CNN_STEPS, CNN_BATCH, CNN_IMG, interpret=interpret, fuse=fuse)
        runs[fuse] = res["losses"]
        print(f"phase B fuse={fuse} losses: {res['losses']} walls (s): {res['walls']}")
    fused, unfused = runs[True], runs[False]
    check(all(math.isfinite(v) for v in fused + unfused), "non-finite CNN loss")
    check(
        np.allclose(fused, unfused, rtol=RTOL, atol=ATOL),
        f"fused CNN losses {fused} != per-node losses {unfused}",
    )


def phase_mesh(cfg, interpret: bool, *, batch: int = LM_BATCH, seq: int = LM_SEQ) -> None:
    """The LM step as a 2x2 data-parallel mesh program on four devices vs
    the same step and batch unsharded on one of them."""
    from repro.lower import (
        NetworkGraph,
        PlanCache,
        lm_token_batches,
        lower_training_step,
        one_hot_rows,
        run_pallas,
        shard_training_step,
    )

    check(jax.device_count() >= 4, f"--chips 4 needs 4 devices, found {jax.device_count()}")
    graph = NetworkGraph.from_model_config(cfg, batch=batch, seq=seq, lr=0.05)
    program = lower_training_step(graph, n_clusters=16)
    sharded = shard_training_step(graph, mesh_shape="2x2", n_clusters=16, program=program)
    x, labels = lm_token_batches(np.random.RandomState(0), batch, seq, cfg.vocab_size)(0)
    inputs = {
        graph.input_edge: x,
        graph.label_edge: one_hot_rows(labels, cfg.vocab_size),
        **graph.init_params(seed=0),
    }
    names = [f"{p}_new" for p in graph.param_shapes()]

    def step(prog, label):
        t0 = time.perf_counter()
        outs = run_pallas(prog, inputs, interpret=interpret, cache=PlanCache())
        jax.block_until_ready(outs)
        devices = {d for n in names for d in outs[n].sharding.device_set}
        new = {n: np.asarray(outs[n]) for n in names}
        print(f"mesh phase {label}: {time.perf_counter() - t0:.1f} s incl. compile, "
              f"outputs on {len(devices)} device(s)")
        return new, devices

    want, one_dev = step(program, "unsharded")
    got, mesh_devs = step(sharded.program, "2x2")
    check(len(one_dev) == 1, f"unsharded step spans {len(one_dev)} devices")
    check(len(mesh_devs) == 4, f"2x2 step spans {len(mesh_devs)} devices, not 4")
    worst = 0.0
    for n in names:
        err = float(np.max(np.abs(got[n] - want[n])) / (np.max(np.abs(want[n])) + 1e-12))
        worst = max(worst, err)
        check(np.allclose(got[n], want[n], rtol=RTOL, atol=ATOL), f"{n}: 2x2 != unsharded ({err:.2e})")
    print(f"mesh phase: {len(names)} updated params agree (worst rel err {worst:.3e})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip smoke needs a TPU; JAX found {dev.platform!r}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import train
    from repro.lower.executors import _resolve_interpret

    print(f"compile cache: {train.use_compile_cache()}")
    print(f"device: {dev.device_kind} x{jax.device_count()}; "
          f"_resolve_interpret(None) = {_resolve_interpret(None)}; running interpret=False")
    clock = CompileClock()
    if args.chips == 4:
        from repro.configs import get_config

        phase_mesh(get_config(LM_ARCH), interpret=False)
    else:
        phase_lm(train, interpret=False)
        print(f"phase A trace/lower/compile s: {clock.snapshot()}")
        phase_cnn(train, interpret=False)
    print(f"trace/lower/compile s, all phases: {clock.snapshot()}")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
    }))


if __name__ == "__main__":
    main()
