"""Train-step factory + CLI driver.

Three gradient-sync modes (EXPERIMENTS.md §Perf compares them):

  * ``auto``       — pure pjit: GSPMD inserts the DP all-reduce and XLA's
                     latency-hiding scheduler overlaps it with the backward
                     pass. This is the beyond-paper optimized path.
  * ``systolic``   — the paper-faithful C6 path: loss+grad run inside a
                     partial-manual shard_map over the DP axes ("pod","data")
                     and gradients are averaged by the explicit 4-wave
                     systolic ring (core/systolic.py), exactly like the
                     mesh-of-HMCs weight update in Fig. 14.
  * ``compressed`` — systolic + int8 error-feedback compression of the
                     gradient stream (optim/compression.py): 4x fewer bytes
                     on the slowest (inter-pod) hop.

Microbatch gradient accumulation (``num_microbatches``) bounds activation
memory — the paper's batch-loop with constant memory footprint (§4.5 note 1).
"""

from __future__ import annotations

import functools
import math
import os
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import systolic
from repro.models import lm
from repro.models.config import ModelConfig, ParallelCtx
from repro.optim import compression
from repro.optim.optimizers import Optimizer, apply_updates, clip_by_global_norm
from repro.parallel import sharding as shd


def _dp_degree(mesh, dp_axes) -> int:
    return math.prod(mesh.shape[a] for a in dp_axes) if dp_axes else 1


def init_train_state(rng, cfg: ModelConfig, optimizer: Optimizer, grad_sync: str = "auto",
                     mesh=None, dp_axes: tuple[str, ...] = ()):
    params = lm.init_lm(rng, cfg)
    state = {"params": params, "opt": optimizer.init(params), "step": jnp.zeros((), jnp.int32)}
    if grad_sync == "compressed":
        dp = _dp_degree(mesh, dp_axes) if mesh is not None else 1
        state["err"] = jax.tree.map(
            lambda p: jnp.zeros((dp,) + p.shape, jnp.float32), params
        )
    return state


def _grads_and_metrics(params, batch, cfg, ctx, num_microbatches):
    """Local (per-dp-shard under systolic; logical under pjit) grads."""

    def loss_fn(p, mb):
        return lm.lm_loss(p, mb, cfg, ctx)

    if num_microbatches <= 1:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return grads, metrics

    def mb_slice(x, i):
        mb = x.shape[0] // num_microbatches
        return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)

    def body(carry, i):
        acc, _ = carry
        mb = jax.tree.map(lambda x: mb_slice(x, i), batch)
        (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)
        return (acc, metrics), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (gsum, metrics), _ = jax.lax.scan(
        body, (zeros, {"loss": 0.0, "ce": 0.0, "load_balance": 0.0, "router_z": 0.0}),
        jnp.arange(num_microbatches),
    )
    grads = jax.tree.map(lambda g, p: (g / num_microbatches).astype(p.dtype), gsum, params)
    return grads, metrics


def make_train_step(
    cfg: ModelConfig,
    ctx: ParallelCtx,
    optimizer: Optimizer,
    *,
    grad_sync: str = "auto",
    num_microbatches: int = 1,
    clip_norm: float | None = 1.0,
):
    mesh, dp_axes = ctx.mesh, ctx.dp_axes

    def finish(state, grads, metrics):
        if mesh is not None:
            # H4 (§Perf): pin gradient shardings to the parameter shardings.
            # Without this GSPMD may materialize full (TP-unsharded) weight
            # gradients inside the backward scan and all-reduce them at full
            # size every layer iteration.
            g_sh = shd.param_shardings(
                jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), grads),
                mesh,
            )
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, g_sh
            )
        if clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            metrics = dict(metrics, grad_norm=gnorm)
        updates, opt = optimizer.update(grads, state["opt"], state["params"])
        params = apply_updates(state["params"], updates)
        new_state = dict(state, params=params, opt=opt, step=state["step"] + 1)
        return new_state, metrics

    if grad_sync == "auto" or mesh is None or not dp_axes:

        def train_step(state, batch):
            grads, metrics = _grads_and_metrics(state["params"], batch, cfg, ctx,
                                                num_microbatches)
            return finish(state, grads, metrics)

        return train_step

    # --- paper-faithful systolic modes -------------------------------------
    dp_sizes = tuple(mesh.shape[a] for a in dp_axes)
    # The systolic wave order is horizontal ("data") then vertical ("pod"),
    # matching Fig. 14 — reverse of the mesh axis order.
    wave_axes = tuple(reversed(dp_axes))
    wave_sizes = tuple(mesh.shape[a] for a in wave_axes)
    inner_ctx = ParallelCtx(
        mesh=mesh, dp_axes=(), tp_axis=ctx.tp_axis, seq_axis=None,
        moe_impl=ctx.moe_impl, attn_backend=ctx.attn_backend, remat=ctx.remat,
        block_kv=ctx.block_kv, ssd_chunk=ctx.ssd_chunk,
    )
    compressed = grad_sync == "compressed"

    def per_shard(params, batch, err):
        grads, metrics = _grads_and_metrics(params, batch, cfg, inner_ctx, num_microbatches)
        new_err = err
        if compressed:
            err0 = jax.tree.map(lambda e: e[0], err)  # drop local leading dim
            grads, _payload, ne = compression.compress_with_feedback(grads, err0)
            new_err = jax.tree.map(lambda e: e[None], ne)
            # int8 wire payload per ring hop (4x fewer bytes on every wave)
            grads = systolic.systolic_mean_tree_q8(grads, wave_axes, wave_sizes)
        else:
            grads = systolic.systolic_mean_tree(grads, wave_axes, wave_sizes)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, dp_axes), metrics)
        return grads, metrics, new_err

    batch_spec_fn = lambda leaf: P(dp_axes, *([None] * (len(leaf.shape) - 1)))

    def train_step(state, batch):
        err = state.get("err", {"_": jnp.zeros((_dp_degree(mesh, dp_axes), 1), jnp.float32)})
        batch_specs = jax.tree.map(lambda x: batch_spec_fn(x), batch)
        err_specs = jax.tree.map(lambda _: P(dp_axes), err)
        grads, metrics, new_err = compat.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(), batch_specs, err_specs),
            out_specs=(P(), P(), err_specs),
            axis_names=set(dp_axes),
            check_vma=False,
        )(state["params"], batch, err)
        new_state, metrics = finish(state, grads, metrics)
        if "err" in state:
            new_state["err"] = new_err
        return new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# Offload-aware step accounting (the paper's runtime view of one train step)
# ---------------------------------------------------------------------------


def offload_step_report(cfg: ModelConfig, seq: int, batch: int, *,
                        n_clusters: int = 16, queue_depth: int = 4,
                        f_ntx: float = 1.5e9) -> dict:
    """Map one training step onto the NTX offload runtime (modeled).

    MACs come from the analytic flop counts, DMA bytes from the HBM-traffic
    model at fp32 stream width (the near-memory tier streams wide); the
    cycle estimate runs the double-buffered runtime of
    :mod:`repro.runtime.scheduler`. The per-layer block lowers the step's
    GEMMs through :func:`repro.lower.lower` — forward plus both training
    passes (dW, dX), the paper's whole-training-layer offload story — and
    the queue-level block maps the dominant forward GEMM onto per-cluster
    command streams to compare queued vs synchronous offload (§2.2).
    """
    from repro.lower import MatmulSpec, NS_DESIGN, lower_layer, run_timing
    from repro.models import flops
    from repro.runtime import scheduler as rt_sched

    macs = flops.train_step_flops(cfg, seq, batch) / 2.0
    dma_bytes = flops.train_hbm_bytes_per_chip(cfg, seq, batch, tp=1, dp=1,
                                               dtype_bytes=4)
    est = rt_sched.simulate_workload(macs, dma_bytes, n_clusters=n_clusters,
                                     f_ntx=f_ntx)

    # per-layer fwd+bwd command accounting from the unified lowering
    tokens = seq * batch
    d_ff = cfg.d_ff or getattr(cfg, "moe_d_ff", 0) or 4 * cfg.d_model
    layer_specs = {
        "attn_qkvo": MatmulSpec(tokens, 4 * cfg.d_model, cfg.d_model),
        "ffn_in": MatmulSpec(tokens, d_ff, cfg.d_model),
        "ffn_out": MatmulSpec(tokens, cfg.d_model, d_ff),
    }
    layers = {}
    layer_progs = {}
    for lname, spec in layer_specs.items():
        progs = layer_progs[lname] = lower_layer(spec)
        # NS-vs-NTX cycle comparison from the timing executor: the NS design
        # re-issues one command per output element (tokens x d_out commands —
        # millions per layer), which only the block-replicated fast path can
        # simulate; split each program over the clusters first (§3.1).
        timed = {}
        for design, prs in (("ntx", progs),
                            ("ns", lower_layer(spec, design=NS_DESIGN))):
            total = 0
            for pr in prs.values():
                # refine only coarse programs (the NTX single-command GEMMs);
                # NS streams are already millions of fine-grained commands
                want = n_clusters * rt_sched.ENGINES_PER_CLUSTER * queue_depth
                if pr.n_commands < want:
                    pr = rt_sched.partition_program(
                        pr, -(-want // pr.n_commands)
                    )
                total += run_timing(pr, n_clusters=n_clusters, f_ntx=f_ntx,
                                    engine="block").total_cycles
            timed[design] = total
        layers[lname] = {
            "offloads": {p: pr.n_offloads for p, pr in progs.items()},
            "busy_cycles": {p: pr.busy_cycles for p, pr in progs.items()},
            "fwd_bwd_offloads": sum(pr.n_offloads for pr in progs.values()),
            "fwd_bwd_cycles_timed": timed["ntx"],
            "fwd_bwd_cycles_timed_ns": timed["ns"],
            "ns_over_ntx_cycles": timed["ns"] / max(timed["ntx"], 1),
        }

    # queue-level view of the dominant GEMM: (tokens x d_ff x d_model)
    gemm = layer_progs["ffn_in"]["fwd"].blocks[0].template
    # enough tiles that every engine's queue can actually fill to queue_depth
    parts = rt_sched.partition_command(
        gemm, n_clusters * rt_sched.ENGINES_PER_CLUSTER * queue_depth
    )
    tile_bytes = [
        (p.loops[2] * p.loops[0] + p.loops[0] * p.loops[1]) * 4 for p in parts
    ]
    sched = rt_sched.MultiClusterScheduler(
        n_clusters=n_clusters,
        cluster=rt_sched.ClusterConfig(queue_depth=queue_depth),
        f_ntx=f_ntx,
    )
    queued = sched.schedule(parts, bytes_per_command=tile_bytes)
    sync_sched = rt_sched.MultiClusterScheduler(
        n_clusters=n_clusters,
        cluster=rt_sched.ClusterConfig(sync=True),
        f_ntx=f_ntx,
    )
    synced = sync_sched.schedule(parts, bytes_per_command=tile_bytes)
    return {
        "macs_per_step": macs,
        "dma_bytes_per_step": dma_bytes,
        "cycles_per_step": est.cycles,
        "step_time_s": est.time,
        "overlap_efficiency": est.overlap_efficiency,
        "layers": layers,
        "gemm_offloads": queued.summary()["n_commands"],
        "gemm_cycles_queued": queued.total_cycles,
        "gemm_cycles_sync": synced.total_cycles,
        "gemm_queued_speedup": synced.total_cycles / max(queued.total_cycles, 1),
        "gemm_utilization": queued.utilization,
    }


# ---------------------------------------------------------------------------
# Sharding helpers for jit/lower
# ---------------------------------------------------------------------------


def state_shardings(state_struct, mesh, dp_axes):
    """NamedSharding tree for a train state (params TP, opt ZeRO-1, err DP)."""
    param_sh = shd.param_shardings(state_struct["params"], mesh)
    opt_sh = shd.opt_state_shardings(state_struct["opt"], mesh, dp_axes)
    out = {
        "params": param_sh,
        "opt": opt_sh,
        "step": NamedSharding(mesh, P()),
    }
    if "err" in state_struct:
        out["err"] = jax.tree.map(
            lambda leaf: NamedSharding(
                mesh, P(dp_axes, *([None] * (len(leaf.shape) - 1)))
            ),
            state_struct["err"],
        )
    return out


def batch_shardings(batch_struct, cfg, mesh, dp_axes, seq_axis=None, batch_size=None):
    def one(leaf):
        b = leaf.shape[0]
        dp = _dp_degree(mesh, dp_axes)
        bspec = dp_axes if (dp_axes and b % dp == 0) else None
        rest = [None] * (len(leaf.shape) - 1)
        if seq_axis is not None and len(leaf.shape) >= 2:
            rest[0] = seq_axis
        return NamedSharding(mesh, P(bspec, *rest))

    return jax.tree.map(one, batch_struct)


# ---------------------------------------------------------------------------
# CLI driver: the production training entrypoint.
#
#   PYTHONPATH=src python -m repro.launch.train --arch qwen1_5_0_5b \
#       --reduced --steps 50 --batch 8 --seq 64 --grad-sync systolic
#
# On a real multi-host deployment jax.distributed.initialize() runs first and
# the same code drives every host; in this container it runs single-process
# (optionally with fake devices via XLA_FLAGS for mesh exercises).
# ---------------------------------------------------------------------------


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and this
    sets nothing. Otherwise the cache goes to ``.jax_cache`` at the root of
    the checkout (git-ignored): a path that never moves, so one run's
    compiled steps are found again by the next. Entry points call this
    before they compile; importing the library never does. Returns the
    directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def validate_mesh_args(mesh: str | None, shard: str, batch: int) -> tuple[int, int] | None:
    """Upfront --mesh / --shard validation with actionable errors.

    Checks everything that would otherwise surface as a deep shard_map or
    splitter failure: mesh spec parses as RxC, the mesh is not degenerate,
    the batch divides over the cubes, and ``--shard 2d`` actually has a
    mesh to shard over. Device-count shortfall is only a note here: the
    interpreter walks the mesh program on one device, and compiled
    kernels refuse it in ``run_pallas``.

    Returns (rows, cols), or None when no mesh was requested.
    """
    from repro.lower.mesh import parse_mesh

    if shard not in ("1d", "2d"):
        raise SystemExit(f"--shard must be '1d' or '2d', got {shard!r}")
    if mesh is None:
        if shard == "2d":
            raise SystemExit(
                "--shard 2d needs a mesh: pass --mesh RxC (rows = pipeline "
                "stages, columns = tensor/data shards), e.g. --mesh 2x2"
            )
        return None
    try:
        rows, cols = parse_mesh(mesh)
    except ValueError as e:
        raise SystemExit(
            f"bad --mesh {mesh!r}: {e} (expected RxC, e.g. --mesh 2x4)"
        ) from None
    if rows < 1 or cols < 1:
        raise SystemExit(
            f"--mesh {mesh!r} is degenerate: both dimensions must be >= 1"
        )
    n = rows * cols
    if batch % n != 0:
        raise SystemExit(
            f"--batch {batch} does not divide over the {rows}x{cols} mesh "
            f"({n} cubes); pick a batch that is a multiple of {n}, e.g. "
            f"--batch {max(n, (batch // n + 1) * n)}"
        )
    n_dev = jax.device_count()
    if n_dev < n:
        print(f"note: {n_dev} jax device(s) < {n} cubes — only the Pallas "
              f"interpreter walks the mesh program on one device (compiled "
              f"kernels refuse it); set "
              f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
              f"for real shard_map execution")
    return rows, cols


def run_ntx_cnn(steps: int, batch: int, img: int, *, n_clusters: int = 16,
                lr: float = 0.05, momentum: float = 0.9,
                interpret: bool | None = None,
                mesh: str | None = None,
                shard: str = "1d",
                metrics: str | None = None,
                trace: str | None = None,
                fuse: bool = True,
                chaos: str | None = None,
                ckpt_dir: str | None = None) -> dict:
    """The ``--backend ntx`` mode: train the paper's small CNN end-to-end
    with every step one compiled :class:`repro.lower.NtxProgram` executed
    through ``run_pallas`` graph execution (cached per-node plans).

    With ``mesh="RxC"`` the step program is sharded across a mesh of HMCs
    (:func:`repro.lower.shard_training_step`): ``run_pallas`` executes it
    data-parallel via ``shard_map`` when enough jax devices exist (e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for a 2x2 mesh
    on CPU), and the modeled mesh timing (per-HMC shard program + eq. 14-15
    link exchange) is printed alongside. ``shard="2d"`` turns the mesh 2D:
    rows become GPipe-style pipeline stages (explicit send/recv blocks on
    the vertical links), columns tensor/data-shard each stage, and the
    modeled timing reports microbatch count and pipeline-bubble fraction.

    ``metrics`` streams one JSON object per step (loss, wall seconds, the
    step's counter totals — :mod:`repro.obs.report` schema); ``trace``
    writes the merged Perfetto trace (cluster exec/DMA lanes, mesh link
    lanes, host lowering/dispatch spans, flow events). Either also prints
    the top-k hotspot table at the end.

    ``fuse`` (default) executes whole-step programs through the region
    fuser — chains of compatible layers as single double-buffered Pallas
    kernels, one cached step-level plan per program. ``fuse=False``
    (``--no-fuse``) is the escape hatch back to per-node plan dispatch.

    ``chaos`` injects faults (:class:`repro.runtime.faults.ChaosSchedule`
    grammar, e.g. ``"kill:hmc=1@step=2"``): a killed cube's step is
    discarded, the program elastically re-shards onto the survivors and
    the step replays, so the run converges to the same gradients as the
    healthy run. Any chaos run (including ``"none"``) switches to
    step-keyed batches — ``batch_fn(i)`` depends only on ``i`` — so a
    replayed step sees bit-identical data; ``ckpt_dir`` enables the
    preemption-rewind path (defaults to ``artifacts/ntx_chaos_ckpt``
    when chaos is on).

    Returns the :func:`repro.lower.train_graph` result dict (program,
    params, losses, per-step walls) plus ``"chaos"`` (the controller's
    report) when chaos was requested.
    """
    from contextlib import nullcontext

    import numpy as np

    from repro import obs
    from repro.lower import (
        PlanCache,
        frequency_band_batches,
        lower_training_step,
        paper_cnn_graph,
        shard_training_step,
        train_graph,
    )
    from repro.lower.executors import _cache_stats

    registry = obs.CounterRegistry() if (metrics or trace) else None
    collector = obs.TraceCollector() if trace else None
    reg_ctx = obs.use_registry(registry) if registry is not None else nullcontext()
    col_ctx = obs.use_collector(collector) if collector is not None else nullcontext()
    with reg_ctx, col_ctx:
        graph = paper_cnn_graph(batch=batch, img=img, lr=lr, momentum=momentum)
        program = lower_training_step(graph, n_clusters=n_clusters)
        print(f"ntx train-step program: {len(program.blocks)} blocks, "
              f"{program.n_commands} commands, "
              f"peak TCDM {program.meta['peak_tcdm_bytes']} / "
              f"{program.meta['tcdm_budget_bytes']} B "
              f"({len(program.meta['spilled'])} spilled)")
        sharded = None
        if mesh is not None:
            from repro.runtime.mesh import time_mesh_step

            sharded = shard_training_step(graph, mesh_shape=mesh,
                                          n_clusters=n_clusters,
                                          program=program, shard=shard)
            program = sharded.program
            n_dev = jax.device_count()
            how = ("shard_map data-parallel" if n_dev >= sharded.n_hmcs
                   else f"single-device walk ({n_dev} jax device(s) "
                        f"< {sharded.n_hmcs} HMCs; interpreter only)")
            print(f"mesh {sharded.mesh_shape[0]}x{sharded.mesh_shape[1]}: "
                  f"{sharded.n_hmcs} HMCs x {sharded.shard_batch} images, "
                  f"{len(program.blocks)} blocks incl. allreduce epilogue; "
                  f"executing via {how}")
            if sharded.shard == "2d":
                pmeta = program.meta["mesh"]["pipeline"]
                stages = [">".join(s) for s in pmeta["stages"]]
                print(f"2d pipeline: {pmeta['n_stages']} stage(s) "
                      f"[{' | '.join(stages)}], "
                      f"{pmeta['n_micro']} microbatch(es), "
                      f"{len(pmeta['xfers'])} boundary transfer(s)")
            tm = time_mesh_step(sharded, n_clusters=n_clusters)
            print(f"modeled mesh step: shard {tm.t_shard*1e3:.3f} ms + "
                  f"update {tm.t_update*1e3:.3f} ms "
                  f"-> speedup {tm.speedup:.2f}, "
                  f"parallel eff {tm.parallel_eff:.1%}")
            if sharded.shard == "2d":
                print(f"2d timing: compute {tm.t_compute*1e3:.3f} ms "
                      f"(bubble {tm.bubble_frac:.1%}), boundary "
                      f"{tm.t_boundary*1e3:.3f} ms (overlapped)")
        chaos_ctl = None
        if chaos is not None:
            from repro.runtime.faults import ChaosController

            # chaos runs need replayable data: key every batch on the step
            # alone so a replayed step sees bit-identical images
            def batch_fn(i):
                rng = np.random.RandomState(10_000 + i)
                return frequency_band_batches(rng, batch, img,
                                              graph.loss.classes)(i)

            chaos_ctl = ChaosController(
                chaos, sharded=sharded,
                ckpt_dir=ckpt_dir or "artifacts/ntx_chaos_ckpt",
                n_clusters=n_clusters,
            )
            print(f"chaos: {chaos!r} (ckpt dir "
                  f"{chaos_ctl.ckpt_dir}, retries "
                  f"{chaos_ctl.retry.max_retries} @ backoff "
                  f"{chaos_ctl.retry.delays()})")
        else:
            batch_fn = frequency_band_batches(np.random.RandomState(0), batch,
                                              img, graph.loss.classes)
        cache = PlanCache()
        res = train_graph(graph, steps, batch_fn, program=program,
                          backend="pallas", interpret=interpret,
                          params=graph.init_params(seed=0),
                          metrics_path=metrics, cache=cache, fuse=fuse,
                          chaos=chaos_ctl)
        if chaos_ctl is not None:
            rep = res["chaos"] = chaos_ctl.report()
            if chaos_ctl.sharded is not None:
                sharded = chaos_ctl.sharded  # trace the surviving mesh
            for line in rep["events"]:
                print(f"chaos event: {line}")
            print(f"chaos report: {rep['remesh_events']} re-shard(s), "
                  f"{rep['preemptions']} preemption(s), "
                  f"{rep['straggler_events']} straggler(s), "
                  f"{rep['recovery_cycles']} modeled recovery cycles, "
                  f"{rep['alive_hmcs']} cube(s) alive at exit")
        if collector is not None:
            if sharded is not None:
                collector.add_mesh_step(sharded, n_clusters=n_clusters)
            else:
                from repro.lower.executors import run_timing

                # The lane-rendering timing run must not double-book the
                # training run's counters.
                with obs.use_registry(None):
                    result = run_timing(program, n_clusters=n_clusters)
                collector.add_cluster_lanes(
                    program, result, n_clusters, pid="hmc0"
                )
                exec_evs = [e for e in collector.events
                            if e.get("cat") == "exec"]
                collector.link_flows(exec_evs, [])
            print(f"merged Perfetto trace: {collector.save(trace)} "
                  f"({len(collector.events)} events) — open in "
                  "https://ui.perfetto.dev")
    losses = res["losses"]
    for i, (loss, w) in enumerate(zip(losses, res["walls"])):
        print(f"step {i:5d} loss={loss:.4f} ({w*1e3:.0f} ms)", flush=True)
    hits, misses, traces, calls = _cache_stats(cache)
    print(f"plan cache: {len(cache)} plans, {traces} traces "
          f"({hits} hits / {misses} misses over {calls} calls)")
    fusion = next(
        iter(program.meta.get("_fusion_plans", {}).values()), None
    )
    if fusion is not None:
        print(f"fusion: {fusion.n_regions} regions + "
              f"{len(fusion.fallback_steps)} fallback steps per step, "
              f"coverage {fusion.coverage:.1%} "
              f"({fusion.fused_commands}/{fusion.total_commands} commands)")
    else:
        print("fusion: disabled (--no-fuse) — per-node plan dispatch")
    if metrics:
        print(f"per-step metrics JSONL: {metrics}")
    if registry is not None:
        print(obs.format_hotspots(registry))
    print(f"done: {steps} ntx steps, loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return res


def _dag_oracle_acts(graph, p, x, relu_masks=None):
    """Any DAG NetworkGraph's forward in plain jax — the ``--check-grads``
    oracle — as every edge's activation by edge name. ``relu_masks`` (node
    name -> bool array) fixes which side of the kink each ReLU element
    takes, for value and derivative alike."""
    from repro.lower import (
        AttentionSpec,
        EmbeddingSpec,
        LayerNormSpec,
        MatmulSpec,
        PosEmbedSpec,
        ReluSpec,
        ResidualAddSpec,
    )

    acts = {graph.input_edge: x}
    for node in graph.nodes:
        s = node.spec
        a = acts[node.in_edge]
        if isinstance(s, (MatmulSpec, EmbeddingSpec)):
            y = a @ p[node.param]
        elif isinstance(s, ReluSpec):
            if relu_masks is None:
                y = jax.nn.relu(a)
            else:
                y = jnp.where(relu_masks[node.name], a, 0.0)
        elif isinstance(s, LayerNormSpec):
            mu = jnp.mean(a, axis=-1, keepdims=True)
            var = jnp.mean((a - mu) ** 2, axis=-1, keepdims=True)
            w = p[node.param]
            y = (a - mu) * jax.lax.rsqrt(var + s.eps) * w[0] + w[1]
        elif isinstance(s, ResidualAddSpec):
            y = a + acts[node.aux_edges[0]]
        elif isinstance(s, PosEmbedSpec):
            y = (a.reshape(s.batch, s.seq, s.d) + p[node.param][None])
            y = y.reshape(-1, s.d)
        elif isinstance(s, AttentionSpec):
            D = s.d

            def one(qkv, s=s, D=D):
                def heads(m):
                    return m.reshape(s.seq, s.n_heads, s.head_dim).transpose(1, 0, 2)

                q, k, v = (heads(qkv[:, i * D:(i + 1) * D]) for i in range(3))
                sc = jnp.einsum("hid,hjd->hij", q, k) * s.scale
                mask = jnp.where(
                    jnp.tril(jnp.ones((s.seq, s.seq), qkv.dtype)) > 0, 0.0, -1e9
                )
                pr = jax.nn.softmax(sc + mask[None], axis=-1)
                ctx = jnp.einsum("hij,hjd->hid", pr, v)
                return ctx.transpose(1, 0, 2).reshape(s.seq, D)

            y = jax.vmap(one)(a.reshape(-1, s.seq, 3 * D)).reshape(-1, D)
        else:  # pragma: no cover - new node types need an oracle rule
            raise TypeError(type(s).__name__)
        acts[node.out_edge] = y
    return acts


def _dag_oracle_logits(graph, p, x, relu_masks=None):
    """The logits of :func:`_dag_oracle_acts`."""
    return _dag_oracle_acts(graph, p, x, relu_masks)[graph.logits_edge]


def _dag_oracle_loss(graph, p, x, onehot, relu_masks=None):
    """Softmax-CE of :func:`_dag_oracle_logits` — the gradient oracle."""
    z = _dag_oracle_logits(graph, p, x, relu_masks)
    return -jnp.mean(jnp.sum(jax.nn.log_softmax(z) * onehot, axis=1))


def _check_lm_grads(graph, program, batch_fn, vocab, *, cache, fuse,
                    interpret) -> dict:
    """Re-run one step of ``program`` from the initial params through
    :func:`run_pallas` — the executor's own route, a mesh program's
    ``shard_map`` and psum included — and hold it to the plain-jax oracle
    at fp32 tolerance (rtol 1e-4, atol 1e-5), in this order:

    1. ReLU sides. Where the step put a ReLU element on the other side of
       the kink from the oracle's own forward, the oracle's pre-activation
       must lie within rtol of zero, relative to the layer's largest
       (``|a| <= 1e-4 * max|a|``): rounding, not a wrong mask.
    2. The logits against the oracle's own forward.
    3. Every ``d_<param>`` elementwise against ``jax.grad`` of
       :func:`_dag_oracle_loss`, the oracle taking each ReLU element on
       the step's side of the kink. Two fp32 evaluations of a deep ReLU
       stack put a few of its tens of millions of pre-activations on
       opposite sides of zero, and each such flip moves a whole gradient
       column by one row's contribution — a full-width 24-layer step and
       the same oracle on another backend disagree that way on about a
       tenth of the params. Check 1 bounds where those flips may lie.

    The oracle runs under ``jax.default_matmul_precision("highest")``: a
    TPU's default f32 matmul rounds to bf16 passes, which is not the fp32
    reference. Raises SystemExit on the first mismatch; returns the worst
    relative gradient error, the number of params checked and the number
    of ReLU elements on the other side of the kink.
    """
    import numpy as np

    from repro.lower import ReluSpec, one_hot_rows, run_pallas

    x, labels = batch_fn(0)
    onehot = one_hot_rows(labels, vocab)
    params = graph.init_params(seed=0)
    inputs = {graph.input_edge: x, graph.label_edge: onehot, **params}
    outs = run_pallas(program, inputs, interpret=interpret, cache=cache,
                      fuse=fuse, relu_outputs=True)
    relus = [n for n in graph.nodes if isinstance(n.spec, ReluSpec)]
    hidden = [n.name for n in relus if n.out_edge not in outs]
    if hidden:
        raise ValueError(f"ReLU outputs of {hidden} ran inside fused regions")
    # on the host: a mesh step leaves them sharded over its devices
    masks = {n.name: np.asarray(outs[n.out_edge]) > 0.0 for n in relus}
    # only the logits and gradients are compared: drop the rest of the
    # step's outputs before the oracle needs device memory
    got_logits = np.asarray(outs[graph.logits_edge])
    got_grads = {p: outs[f"d_{p}"] for p in graph.param_shapes()}
    del outs

    def forward(p, x):
        acts = _dag_oracle_acts(graph, p, x)
        return acts[graph.logits_edge], {n.name: acts[n.in_edge] for n in relus}

    with jax.default_matmul_precision("highest"):
        want_logits, pre = jax.jit(forward)(params, x)
    flips, total, worst_side = 0, 0, 0.0
    for n in relus:
        a = np.asarray(pre.pop(n.name))
        off = masks[n.name] != (a > 0.0)
        total += a.size
        if off.any():
            flips += int(off.sum())
            side = float(np.abs(a[off]).max() / np.abs(a).max())
            worst_side = max(worst_side, side)
            if side > 1e-4:
                raise SystemExit(
                    f"ReLU check FAILED for {n.name}: {int(off.sum())} elements "
                    f"on the other side of the kink, up to {side:.2e} of max|a|"
                )
    print(f"ReLU sides: {flips} of {total} elements differ from the oracle's, "
          f"all within {worst_side:.2e} of their layer's max|a|")
    if not np.allclose(got_logits, np.asarray(want_logits), rtol=1e-4, atol=1e-5):
        raise SystemExit("forward check FAILED: logits differ from the oracle")
    with jax.default_matmul_precision("highest"):
        want_grads = jax.jit(jax.grad(
            lambda p, x, y, m: _dag_oracle_loss(graph, p, x, y, m)
        ))(params, x, onehot, masks)
    worst = 0.0
    for p in graph.param_shapes():
        got = np.asarray(got_grads.pop(p))
        want = np.asarray(want_grads.pop(p))
        rel = float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-12))
        worst = max(worst, rel)
        if not np.allclose(got, want, rtol=1e-4, atol=1e-5):
            raise SystemExit(f"gradient check FAILED for {p}: rel err {rel:.2e}")
    n = len(graph.param_shapes())
    print(f"gradient check vs jax.grad: {n} params OK (worst rel err {worst:.2e})")
    return {"worst_rel_err": worst, "n_params": n, "relu_flips": flips}


def run_ntx_lm(model: str, steps: int, batch: int, seq: int, *,
               n_clusters: int = 16, lr: float = 0.05,
               reduced: bool = True,
               interpret: bool | None = None,
               mesh: str | None = None,
               shard: str = "1d",
               metrics: str | None = None,
               trace: str | None = None,
               fuse: bool = True,
               check_grads: bool = False) -> dict:
    """The ``--backend ntx --model <config>`` mode: train a small
    decoder-only transformer end-to-end, every step one compiled
    :class:`repro.lower.NtxProgram`.

    The named :class:`~repro.models.config.ModelConfig` (``repro.configs``
    registry) is shrunk to smoke scale (``reduced``, the default) and built
    into a DAG training graph by :meth:`NetworkGraph.from_model_config` —
    embedding, learned positions, pre-LN attention + FFN blocks with
    residual fan-out, final norm, tied-free head — then trained on the
    synthetic next-token task of :func:`repro.lower.lm_token_batches`
    through the same ``run_pallas`` plan-cache execution as the CNN path.
    The block-engine timing run prints Table-2-style offload/command/cycle
    counts for the LM step; with ``mesh="RxC"`` the step program shards
    across the HMC mesh and :func:`repro.runtime.mesh.time_mesh_step`
    reports the modeled mesh step alongside.

    ``check_grads`` re-runs one step and verifies every ``d_<param>``
    against ``jax.grad`` of the plain-jax graph oracle at fp32 tolerance —
    the CI lm-train-smoke gate; the result then carries ``"grad_check"``
    (worst relative error, params checked).
    """
    from contextlib import nullcontext

    import numpy as np

    from repro import obs
    from repro.configs import get_config, reduce_config
    from repro.lower import (
        NetworkGraph,
        PlanCache,
        lm_token_batches,
        lower_training_step,
        run_timing,
        shard_training_step,
        train_graph,
    )
    from repro.lower.executors import _cache_stats

    cfg = get_config(model)
    if reduced:
        cfg = reduce_config(cfg)
    else:
        print(f"note: lowering the FULL {cfg.name} config — expect a very "
              f"large program; --reduced is the smoke-scale path")
    registry = obs.CounterRegistry() if (metrics or trace) else None
    collector = obs.TraceCollector() if trace else None
    reg_ctx = obs.use_registry(registry) if registry is not None else nullcontext()
    col_ctx = obs.use_collector(collector) if collector is not None else nullcontext()
    with reg_ctx, col_ctx:
        graph = NetworkGraph.from_model_config(cfg, batch=batch, seq=seq, lr=lr)
        program = lower_training_step(graph, n_clusters=n_clusters)
        print(f"ntx LM train-step program ({graph.name}): "
              f"{len(graph.nodes)} nodes -> {len(program.blocks)} blocks, "
              f"{program.n_commands} commands, "
              f"peak TCDM {program.meta['peak_tcdm_bytes']} / "
              f"{program.meta['tcdm_budget_bytes']} B "
              f"({len(program.meta['spilled'])} spilled)")
        # Table-2-style step accounting from the timing engine
        with obs.use_registry(None):
            timed = run_timing(program, n_clusters=n_clusters, engine="block")
        print(f"timing engine: {program.n_offloads} offloads, "
              f"{program.n_commands} commands, "
              f"{timed.total_cycles} cycles/step on {n_clusters} clusters")
        sharded = None
        if mesh is not None:
            from repro.runtime.mesh import time_mesh_step

            sharded = shard_training_step(graph, mesh_shape=mesh,
                                          n_clusters=n_clusters,
                                          program=program, shard=shard)
            program = sharded.program
            n_dev = jax.device_count()
            how = ("shard_map data-parallel" if n_dev >= sharded.n_hmcs
                   else f"single-device walk ({n_dev} jax device(s) "
                        f"< {sharded.n_hmcs} HMCs; interpreter only)")
            print(f"mesh {sharded.mesh_shape[0]}x{sharded.mesh_shape[1]}: "
                  f"{sharded.n_hmcs} HMCs x {sharded.shard_batch} sequences, "
                  f"{len(program.blocks)} blocks incl. allreduce epilogue; "
                  f"executing via {how}")
            tm = time_mesh_step(sharded, n_clusters=n_clusters)
            print(f"modeled mesh step: shard {tm.t_shard*1e3:.3f} ms + "
                  f"update {tm.t_update*1e3:.3f} ms "
                  f"-> speedup {tm.speedup:.2f}, "
                  f"parallel eff {tm.parallel_eff:.1%}")
        batch_fn = lm_token_batches(np.random.RandomState(0), batch, seq,
                                    cfg.vocab_size)
        cache = PlanCache()
        res = train_graph(graph, steps, batch_fn, program=program,
                          backend="pallas", interpret=interpret,
                          params=graph.init_params(seed=0),
                          metrics_path=metrics, cache=cache, fuse=fuse,
                          relu_outputs=check_grads)
        if collector is not None:
            if sharded is not None:
                collector.add_mesh_step(sharded, n_clusters=n_clusters)
            else:
                with obs.use_registry(None):
                    result = run_timing(program, n_clusters=n_clusters)
                collector.add_cluster_lanes(
                    program, result, n_clusters, pid="hmc0"
                )
                exec_evs = [e for e in collector.events
                            if e.get("cat") == "exec"]
                collector.link_flows(exec_evs, [])
            print(f"merged Perfetto trace: {collector.save(trace)} "
                  f"({len(collector.events)} events) — open in "
                  "https://ui.perfetto.dev")
    losses = res["losses"]
    for i, (loss, w) in enumerate(zip(losses, res["walls"])):
        print(f"step {i:5d} loss={loss:.4f} ({w*1e3:.0f} ms)", flush=True)
    hits, misses, traces, calls = _cache_stats(cache)
    print(f"plan cache: {len(cache)} plans, {traces} traces "
          f"({hits} hits / {misses} misses over {calls} calls)")
    fusion = next(
        iter(program.meta.get("_fusion_plans", {}).values()), None
    )
    if fusion is not None:
        print(f"fusion: {fusion.n_regions} regions + "
              f"{len(fusion.fallback_steps)} fallback steps per step, "
              f"coverage {fusion.coverage:.1%} "
              f"({fusion.fused_commands}/{fusion.total_commands} commands) — "
              f"token-row graphs fuse update epilogues only")
    else:
        print("fusion: disabled (--no-fuse) — per-node plan dispatch")
    if check_grads:
        res["grad_check"] = _check_lm_grads(
            graph, res["program"], batch_fn, cfg.vocab_size,
            cache=cache, fuse=fuse, interpret=interpret,
        )
    if metrics:
        print(f"per-step metrics JSONL: {metrics}")
    if registry is not None:
        print(obs.format_hotspots(registry))
    print(f"done: {steps} LM ntx steps, loss "
          f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    return res


def _cli():
    import argparse
    import time

    use_compile_cache()

    from repro.configs import get_config, reduce_config
    from repro.data.pipeline import DataIterator, InMemoryDataset
    from repro.models.config import ParallelCtx
    from repro.optim.optimizers import get_optimizer
    from repro.runtime.supervisor import FailureInjector, Supervisor

    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="xla", choices=["xla", "ntx"],
                    help="xla: the LM training CLI below; ntx: train the "
                         "paper's small CNN — or, with --model, a small "
                         "decoder-only transformer — with one compiled "
                         "NtxProgram per step (run_pallas graph execution)")
    ap.add_argument("--img", type=int, default=16,
                    help="ntx backend: CNN input image size")
    ap.add_argument("--model", default=None, metavar="ARCH",
                    help="ntx backend: instead of the CNN, train a small "
                         "decoder-only transformer built from this "
                         "repro.configs ModelConfig name (e.g. "
                         "qwen1_5_0_5b) via "
                         "NetworkGraph.from_model_config; combine with "
                         "--reduced for the smoke-scale config")
    ap.add_argument("--check-grads", action="store_true",
                    help="ntx --model: after training, re-run one step and "
                         "verify every parameter gradient against jax.grad "
                         "of the plain-jax graph oracle at fp32 tolerance")
    ap.add_argument("--mesh", default=None, metavar="RxC",
                    help="ntx backend: shard the train step across an RxC "
                         "mesh of HMCs (batch must divide evenly); executes "
                         "data-parallel via shard_map when enough jax "
                         "devices exist and prints the modeled mesh timing")
    ap.add_argument("--shard", default="1d", choices=["1d", "2d"],
                    help="ntx backend: mesh sharding layout. 1d: pure data "
                         "parallelism (every cube runs the whole model on a "
                         "batch slice). 2d: mesh rows are GPipe-style "
                         "pipeline stages with explicit send/recv link "
                         "traffic, columns tensor/data-shard each stage — "
                         "for models that don't fit one HMC")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="ntx backend: inject faults — 'kill:hmc=1@step=2', "
                         "'straggle:hmc=0,slow=4@step=3', 'preempt@step=5' "
                         "(join with ';'), or "
                         "'random:seed=7,p_kill=0.02'. A killed cube's step "
                         "is discarded, the program re-shards onto the "
                         "survivors and the step replays; a preemption "
                         "rewinds to the latest checkpoint. 'none' enables "
                         "the (step-keyed) chaos data path without faults — "
                         "the healthy baseline for chaos diffs")
    ap.add_argument("--chaos-ckpt", default=None, metavar="DIR",
                    help="ntx backend: checkpoint dir the chaos controller "
                         "owns (wiped at start; default "
                         "artifacts/ntx_chaos_ckpt)")
    ap.add_argument("--arch", default="qwen1_5_0_5b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--optimizer", default="adamw", choices=["sgd", "adamw"])
    ap.add_argument("--lr", type=float, default=None,
                    help="learning rate (default: 3e-3 for the xla AdamW "
                         "driver, 0.05 for ntx SGD)")
    ap.add_argument("--grad-sync", default="auto",
                    choices=["auto", "systolic", "compressed"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="artifacts/train_cli_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--crash-at", type=int, default=None)
    ap.add_argument("--offload-report", action="store_true",
                    help="print the modeled NTX offload accounting for one "
                         "train step (queue/DMA runtime) and compare it with "
                         "the measured step time at the end")
    ap.add_argument("--offload-clusters", type=int, default=16)
    ap.add_argument("--queue-depth", type=int, default=4)
    ap.add_argument("--metrics", default=None, metavar="OUT.jsonl",
                    help="stream per-step metrics (loss/wall/counter totals) "
                         "as JSON lines to this path (both backends)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="ntx backend: write the merged Perfetto trace "
                         "(cluster exec/DMA + mesh link + host lanes) here")
    ap.add_argument("--no-fuse", action="store_true",
                    help="ntx backend: disable the region fuser and run "
                         "per-node plan dispatch (the pre-fusion walk); "
                         "numerics are identical, steps are slower")
    args = ap.parse_args()

    if args.backend == "ntx":
        validate_mesh_args(args.mesh, args.shard, args.batch)
        lr = 0.05 if args.lr is None else args.lr
        if args.model is not None:
            if args.chaos is not None:
                raise SystemExit("--chaos is CNN-path only for now; "
                                 "drop it or drop --model")
            res = run_ntx_lm(args.model, args.steps, args.batch, args.seq,
                             n_clusters=args.offload_clusters,
                             lr=lr, reduced=args.reduced,
                             mesh=args.mesh, shard=args.shard,
                             metrics=args.metrics, trace=args.trace,
                             fuse=not args.no_fuse,
                             check_grads=args.check_grads)
            if (len(res["losses"]) >= 3
                    and not res["losses"][-1] < res["losses"][0]):
                raise SystemExit("ntx LM training did not decrease the loss")
            return
        res = run_ntx_cnn(args.steps, args.batch, args.img, lr=lr,
                          n_clusters=args.offload_clusters, mesh=args.mesh,
                          shard=args.shard,
                          metrics=args.metrics, trace=args.trace,
                          fuse=not args.no_fuse, chaos=args.chaos,
                          ckpt_dir=args.chaos_ckpt)
        if len(res["losses"]) >= 3 and not res["losses"][-1] < res["losses"][0]:
            raise SystemExit("ntx CNN training did not decrease the loss")
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if cfg.input_mode == "embeddings":
        raise SystemExit("CLI driver trains token-input archs; use examples/ for stubs")

    n_dev = jax.device_count()
    if n_dev > 1:
        model = math.gcd(n_dev, 4)
        mesh = compat.make_mesh((n_dev // model, model), ("data", "model"))
        dp_axes = ("data",)
    else:
        mesh, dp_axes = None, ()
    ctx = ParallelCtx(mesh=mesh, dp_axes=dp_axes,
                      tp_axis="model" if mesh else None, attn_backend="xla",
                      grad_sync=args.grad_sync)

    opt = get_optimizer(args.optimizer, 3e-3 if args.lr is None else args.lr)
    ds = InMemoryDataset.synthetic(2_000_000, cfg.vocab_size, args.seq, seed=0)
    iterator = DataIterator(ds, batch_size=args.batch, seed=0)

    def init_state(_mesh):
        return init_train_state(jax.random.PRNGKey(0), cfg, opt, args.grad_sync,
                                mesh, dp_axes)

    def make_step(_mesh):
        return jax.jit(
            make_train_step(cfg, ctx, opt, grad_sync=args.grad_sync,
                            num_microbatches=args.microbatches),
            donate_argnums=(0,),
        )

    offload = None
    if args.offload_report:
        offload = offload_step_report(cfg, args.seq, args.batch,
                                      n_clusters=args.offload_clusters,
                                      queue_depth=args.queue_depth)
        print("offload step accounting (modeled NTX runtime):")
        for key, v in offload.items():
            if key == "layers":
                print("  per-layer fwd+bwd offloads (lowered programs):")
                for lname, info in v.items():
                    offs = info["offloads"]
                    print(f"    {lname}: fwd={offs['fwd']} dw={offs['dw']} "
                          f"dx={offs['dx']} total={info['fwd_bwd_offloads']} "
                          f"timed_cycles={info['fwd_bwd_cycles_timed']} "
                          f"ns/ntx={info['ns_over_ntx_cycles']:.2f}x")
            else:
                print(f"  {key}: {v:.4g}" if isinstance(v, float)
                      else f"  {key}: {v}")

    injector = FailureInjector({args.crash_at: "crash"} if args.crash_at else {})
    t0 = time.time()

    def cb(step, metrics):
        if step % 10 == 0:
            print(f"step {step:5d} ce={float(metrics['ce']):.4f} "
                  f"({time.time() - t0:.0f}s)", flush=True)

    registry = None
    if args.metrics:
        from repro.obs import CounterRegistry

        registry = CounterRegistry()
    sup = Supervisor(make_step, init_state, iterator, args.ckpt_dir,
                     ckpt_every=args.ckpt_every, injector=injector,
                     registry=registry, metrics_path=args.metrics)
    report = sup.run(args.steps, metrics_cb=cb)
    print(f"done: {report.steps_run} steps, {report.restarts} restarts")
    if args.metrics:
        print(f"per-step metrics JSONL: {args.metrics}")
    if offload is not None and report.steps_run:
        measured = (time.time() - t0) / report.steps_run
        print(f"offload model: {offload['step_time_s']*1e3:.2f} ms/step modeled "
              f"on {args.offload_clusters} clusters vs {measured*1e3:.2f} ms/step "
              f"measured on {jax.default_backend()}")


if __name__ == "__main__":
    _cli()
