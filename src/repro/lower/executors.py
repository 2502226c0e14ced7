"""Three interchangeable executors over one :class:`NtxProgram`.

  * :func:`run_reference` — the behavioural model: place the inputs in a flat
    numpy TCDM, run every command through
    :func:`repro.core.ntx.ntx_execute` (vectorized fast path by default),
    read the outputs back. Ground truth for the other two.
  * :func:`run_timing` — the performance model: feed the block structure (or
    the exact command stream) + per-command DMA descriptors to
    :class:`repro.runtime.scheduler.MultiClusterScheduler` and return its
    :class:`ScheduleResult`. Programs above ~50k commands take the
    block-replicated steady-state path automatically — identical cycle
    counts, O(blocks) wall time — so million-command NS-design convs are
    cheap to time.
  * :func:`run_pallas` — the production backend: route the lowered layer to
    the Pallas kernels (:mod:`repro.kernels.streaming`,
    :mod:`repro.kernels.ops`) through a process-wide :class:`PlanCache` of
    jitted whole-pass executables, so "one offload" becomes "one cached
    pallas_call" — zero retraces after warmup.

All three consume the same lowered program — a new layer type needs one
lowering rule, not three backend implementations.
"""

from __future__ import annotations

import numpy as np

from dataclasses import dataclass

from repro.core.ntx import ntx_execute
from repro.lower.ir import NtxProgram
from repro.obs import counters as obs
from repro.obs import trace as obs_trace
from repro.lower.rules import (
    AttentionSpec,
    BiasSpec,
    Conv2dSpec,
    EmbeddingSpec,
    FlattenSpec,
    LayerNormSpec,
    MatmulSpec,
    MaxPool2dSpec,
    PosEmbedSpec,
    ReluSpec,
    ResidualAddSpec,
    SgdUpdateSpec,
    SoftmaxXentSpec,
)


@dataclass(frozen=True)
class BatchedSpec:
    """A per-image spec run over the leading batch axis.

    The graph executor uses this as the plan-cache key for per-image layer
    nodes (conv/pool) executing over a whole batch. Convs fold the images
    into the streamed matmul rows, and their dW comes out summed over the
    batch; other specs vmap, with parameters broadcast and everything else
    mapped over axis 0.
    """

    spec: object
    batch: int

# ---------------------------------------------------------------------------
# 1. Reference executor (numpy TCDM + the ntx_execute interpreter)
# ---------------------------------------------------------------------------


def run_reference(
    program: NtxProgram,
    inputs: dict[str, np.ndarray],
    *,
    wide: bool = True,
    vectorize: bool = True,
) -> dict[str, np.ndarray]:
    """Execute ``program`` against a flat TCDM; return its output regions.

    ``inputs`` maps region names (kind "input"/"param") to arrays of the
    region's shape. Scratch regions are staged by the program's own
    memset/copy commands — no out-of-band padding happens here.
    """
    mem = np.zeros(program.memory_words, np.float32)
    needed = {r.name for r in program.regions.values() if r.kind in ("input", "param")}
    missing = needed - set(inputs)
    if missing:
        raise ValueError(f"missing input regions: {sorted(missing)}")
    for name, arr in inputs.items():
        r = program.region(name)
        a = np.asarray(arr, np.float32)
        if a.shape != r.shape:
            raise ValueError(f"region {name!r} expects shape {r.shape}, got {a.shape}")
        mem[r.base : r.end] = a.ravel()
    for cmd in program.commands():
        ntx_execute(cmd, mem, wide=wide, vectorize=vectorize, inplace=True)
    obs.record_program(obs.get_active(), program)
    return {
        r.name: mem[r.base : r.end].reshape(r.shape).copy()
        for r in program.regions_of_kind("output")
    }


# ---------------------------------------------------------------------------
# 2. Timing executor (event-driven queue/DMA runtime, block fast path)
# ---------------------------------------------------------------------------


def run_timing(
    program: NtxProgram,
    *,
    n_clusters: int = 1,
    cluster=None,
    f_ntx: float = 1.5e9,
    engine: str = "auto",
    exec_cycles=None,
):
    """Simulate ``program`` on the offload runtime; returns a ScheduleResult.

    The command stream and the per-command input-DMA byte counts both come
    straight from the lowered program, so the timing model sees exactly what
    the reference interpreter executes. ``engine`` picks the simulation
    strategy (``"auto"`` | ``"event"`` | ``"block"``): the block-replicated
    steady-state path gives cycle counts identical to the event-driven
    engine in O(blocks) time, so there is no program-size cap — NS-design
    convs with millions of commands simulate in milliseconds.
    ``exec_cycles`` optionally overrides per-command datapath cycles (must
    not depend on AGU bases on the block path).
    """
    from repro.runtime import scheduler as rt_sched

    sched = rt_sched.MultiClusterScheduler(
        n_clusters=n_clusters, cluster=cluster, f_ntx=f_ntx
    )
    result = sched.schedule_program(program, engine=engine, exec_cycles=exec_cycles)
    reg = obs.get_active()
    if reg is not None:
        obs.record_program(reg, program)
        obs.record_schedule(reg, result)
    return result


# ---------------------------------------------------------------------------
# 3. Pallas executor (kernels/streaming.py + kernels/ops.py, plan cache)
# ---------------------------------------------------------------------------


def _conv_plan(spec: Conv2dSpec, pass_: str, interpret: bool):
    """One conv pass over a batch of images (N, H, W, C) in the streaming
    kernel, or None for a pass the conv has no route for.

    The images fold into the matmul rows, so dW comes out summed over the
    batch (the images join the contraction, as in the fused region).
    """
    import jax.numpy as jnp

    from repro.core import conv_decomp
    from repro.kernels import streaming

    s, p = spec.stride, spec.padding
    if pass_ == "fwd":
        return lambda j: {
            "y": streaming.streaming_conv2d(
                j["x"], j["w"], stride=s, padding=p, interpret=interpret
            )
        }
    if pass_ == "dw":
        # dW = cols(x)^T @ dy: the same im2col the forward kernel streams,
        # with the (n*oh*ow) output pixels as the contraction dim.
        def dw(j):
            x = j["x"]
            if p:
                x = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
            oh, ow = spec.out_h, spec.out_w
            cols = jnp.concatenate(
                [
                    x[:, dh : dh + oh * s : s, dw_ : dw_ + ow * s : s, :]
                    for dh in range(spec.kh)
                    for dw_ in range(spec.kw)
                ],
                axis=-1,
            ).reshape(-1, spec.kh * spec.kw * spec.cin)
            dyf = j["dy"].reshape(-1, spec.cout)
            dw_flat = streaming.streaming_matmul(cols.T, dyf, interpret=interpret)
            return {"dw": dw_flat.reshape(spec.kh, spec.kw, spec.cin, spec.cout)}

        return dw
    if pass_ == "dx":
        # The §3.2 phase decomposition with the dense per-phase conv
        # routed through the streaming Pallas kernel.
        def conv_fn(dy, w_ab, pads):
            ph, pw = pads
            dyp = jnp.pad(dy, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
            return streaming.streaming_conv2d(
                dyp, w_ab, stride=1, padding=0, interpret=interpret
            )

        return lambda j: {
            "dx": conv_decomp.conv2d_input_grad_decomposed(
                j["dy"], j["w"], s, (spec.in_h, spec.in_w), p, conv_fn=conv_fn
            )
        }
    return None


def _plan_callable(spec, pass_: str, interpret: bool):
    """Pure jax function dict[str, Array] -> dict[str, Array] for one plan.

    Shapes/strides are baked in from ``spec`` (hashable frozen dataclasses),
    so one callable serves every invocation of that (spec, pass) — this is
    what :class:`PlanCache` jits and keeps.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import streaming
    from repro.lower.fuse import RegionSpec

    if isinstance(spec, RegionSpec):
        # one fused kernel for a whole region chain; ``pass_`` is "region"
        from repro.kernels import fused

        return fused.build_region_callable(spec, interpret=interpret)

    if isinstance(spec, MatmulSpec):
        if pass_ == "fwd":
            return lambda j: {
                "c": streaming.streaming_matmul(j["a"], j["b"], interpret=interpret)
            }
        if pass_ == "dw":
            return lambda j: {
                "dw": streaming.streaming_matmul(j["a"].T, j["dy"], interpret=interpret)
            }
        if pass_ == "dx":
            return lambda j: {
                "dx": streaming.streaming_matmul(j["dy"], j["b"].T, interpret=interpret)
            }

    if isinstance(spec, Conv2dSpec):
        conv = _conv_plan(spec, pass_, interpret)
        if conv is not None:
            # one image: lend it a batch axis of one and take it back off
            # (dW has no batch axis to remove)
            def one_image(j):
                out = conv({k: v if k == "w" else v[None] for k, v in j.items()})
                return {k: v if k == "dw" else v[0] for k, v in out.items()}

            return one_image

    if isinstance(spec, MaxPool2dSpec):
        w, s = spec.window, spec.stride

        def pool_fwd(x):
            return jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (w, w, 1), (s, s, 1), "VALID"
            )

        if pass_ == "fwd":
            return lambda j: {"y": pool_fwd(j["x"])}
        if pass_ == "dx":

            def pool_dx(j):
                _, vjp = jax.vjp(pool_fwd, j["x"])
                return {"dx": vjp(j["dy"])[0]}

            return pool_dx

    if isinstance(spec, ReluSpec):
        if pass_ == "fwd":
            return lambda j: {"y": jnp.maximum(j["x"], 0.0)}
        if pass_ == "dx":
            # the sign/select mask pattern of the lowering rule, in jnp
            return lambda j: {"dx": jnp.where(j["x"] > 0.0, j["dy"], 0.0)}

    if isinstance(spec, BiasSpec):
        if pass_ == "fwd":
            return lambda j: {"y": j["x"] + j["b"][None, :]}
        if pass_ == "dw":
            return lambda j: {"db": j["dy"].sum(axis=0)}
        if pass_ == "dx":
            return lambda j: {"dx": j["dy"]}

    if isinstance(spec, SoftmaxXentSpec):
        if pass_ == "dx":
            B = spec.batch

            def xent_dx(j):
                p = jax.nn.softmax(j["z"], axis=-1)
                return {"dz": (p - j["onehot"]) / B}

            return xent_dx

    if isinstance(spec, SgdUpdateSpec):
        if pass_ == "upd":
            lr, mu = spec.lr, spec.momentum
            if mu:

                def upd_mom(j):
                    v_new = mu * j["v"] + j["dw"]
                    return {"v_new": v_new, "w_new": j["w"] - lr * v_new}

                return upd_mom
            return lambda j: {"w_new": j["w"] - lr * j["dw"]}

    if isinstance(spec, AttentionSpec):
        S, H, Dh = spec.seq, spec.n_heads, spec.head_dim
        D = H * Dh

        def attn_one(x):  # (S, 3D) qkv -> (S, D) context, causal
            q = x[:, :D].reshape(S, H, Dh).transpose(1, 0, 2)
            k = x[:, D:2 * D].reshape(S, H, Dh).transpose(1, 0, 2)
            v = x[:, 2 * D:].reshape(S, H, Dh).transpose(1, 0, 2)
            # full fp32 contractions: the TPU default rounds f32 to bf16
            hi = jax.lax.Precision.HIGHEST
            sc = jnp.einsum("hid,hjd->hij", q, k, precision=hi) * spec.scale
            mask = jnp.where(
                jnp.tril(jnp.ones((S, S), x.dtype)) > 0, 0.0, -1e9
            )
            p = jax.nn.softmax(sc + mask[None], axis=-1)
            ctx = jnp.einsum("hij,hjd->hid", p, v, precision=hi)
            return ctx.transpose(1, 0, 2).reshape(S, D)

        if pass_ == "fwd":
            return lambda j: {"y": attn_one(j["x"])}
        if pass_ == "dx":

            def attn_dx(j):
                _, vjp = jax.vjp(attn_one, j["x"])
                return {"dx": vjp(j["dy"])[0]}

            return attn_dx

    if isinstance(spec, LayerNormSpec):
        eps = spec.eps

        def ln_xhat(j):
            mu = jnp.mean(j["x"], axis=-1, keepdims=True)
            var = jnp.mean((j["x"] - mu) ** 2, axis=-1, keepdims=True)
            return (j["x"] - mu) * jax.lax.rsqrt(var + eps)

        if pass_ == "fwd":
            return lambda j: {"y": ln_xhat(j) * j["w"][0] + j["w"][1]}
        if pass_ == "dw":
            return lambda j: {
                "dw": jnp.stack(
                    [(j["dy"] * ln_xhat(j)).sum(axis=0), j["dy"].sum(axis=0)]
                )
            }
        if pass_ == "dx":

            def ln_dx(j):
                xhat = ln_xhat(j)
                mu = jnp.mean(j["x"], axis=-1, keepdims=True)
                var = jnp.mean((j["x"] - mu) ** 2, axis=-1, keepdims=True)
                dyg = j["dy"] * j["w"][0]
                m1 = jnp.mean(dyg, axis=-1, keepdims=True)
                m2 = jnp.mean(dyg * xhat, axis=-1, keepdims=True)
                return {
                    "dx": (dyg - m1 - xhat * m2) * jax.lax.rsqrt(var + eps)
                }

            return ln_dx

    if isinstance(spec, ResidualAddSpec):
        if pass_ == "fwd":
            return lambda j: {"y": j["x"] + j["x2"]}
        if pass_ == "dx":
            return lambda j: {"dx": j["dy"]}

    if isinstance(spec, EmbeddingSpec):
        if pass_ == "fwd":
            return lambda j: {
                "y": streaming.streaming_matmul(j["x"], j["w"],
                                                interpret=interpret)
            }
        if pass_ == "dw":
            return lambda j: {
                "dw": streaming.streaming_matmul(j["x"].T, j["dy"],
                                                 interpret=interpret)
            }

    if isinstance(spec, PosEmbedSpec):
        if pass_ == "fwd":
            return lambda j: {"y": j["x"] + j["w"][None]}
        if pass_ == "dw":
            return lambda j: {"dw": j["dy"].sum(axis=0)}
        if pass_ == "dx":
            return lambda j: {"dx": j["dy"]}

    if isinstance(spec, BatchedSpec):
        if isinstance(spec.spec, Conv2dSpec):
            # the images fold into the streamed matmul rows: a vmap would
            # hand the ANY-space streaming kernel a squeezed batch block,
            # which Mosaic refuses
            conv = _conv_plan(spec.spec, pass_, interpret)
            if conv is not None:
                return conv
        inner = _plan_callable(spec.spec, pass_, interpret)

        def batched(j):
            axes = {k: (None if k in ("w", "b") else 0) for k in j}
            return jax.vmap(inner, in_axes=(axes,))(j)

        return batched

    raise TypeError(
        f"no Pallas route for spec {type(spec).__name__} pass {pass_!r}"
    )


class CompiledPlan:
    """One jitted whole-pass executable plus its jax trace counter.

    ``traces`` increments each time jax (re-)traces the underlying function
    — after warmup on fixed shapes it must stay at 1, which the tests and
    the ``pallas_plan_cache`` benchmark assert.
    """

    __slots__ = ("key", "fn", "traces", "calls")

    def __init__(self, key):
        self.key = key
        self.fn = None
        self.traces = 0
        self.calls = 0

    def __call__(self, inputs):
        self.calls += 1
        return self.fn(inputs)


class PlanCache:
    """Compiled-program cache for the Pallas executor.

    Keyed by ``(spec, pass, design, interpret)`` — specs are frozen
    dataclasses carrying every static shape/stride, so two programs lowered
    from equal specs share one jitted executable. The cache is unbounded
    (one entry per distinct layer shape in the process); :meth:`clear`
    drops everything.
    """

    def __init__(self):
        self._plans: dict = {}
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._plans)

    def clear(self) -> None:
        self._plans.clear()
        self.hits = 0
        self.misses = 0

    def get(self, spec, pass_: str, design: str, interpret: bool) -> CompiledPlan:
        interpret = bool(interpret)
        return self.get_fn(
            (spec, pass_, design, interpret),
            lambda: _plan_callable(spec, pass_, interpret),
        )

    def get_fn(self, key, build) -> CompiledPlan:
        """A cached jitted plan for an arbitrary hashable key.

        ``build`` runs once per key to produce the raw jax callable.
        :meth:`get` routes per-node plans through here with
        ``(spec, pass, design, interpret)`` keys; the fused graph executor
        caches whole-train-step callables under step-level keys the same
        way, so the retrace/hit accounting covers both granularities.
        """
        plan = self._plans.get(key)
        if plan is not None:
            self.hits += 1
            return plan
        self.misses += 1
        import jax

        plan = CompiledPlan(key)
        raw = build()

        def counted(j):
            plan.traces += 1
            return raw(j)

        plan.fn = jax.jit(counted)
        self._plans[key] = plan
        return plan


#: Process-wide default cache; pass ``cache=`` to isolate (tests, benchmarks).
PLAN_CACHE = PlanCache()


def _cache_stats(cache: PlanCache) -> tuple[int, int, int, int]:
    """(hits, misses, traces, calls) — the plan-cache counter snapshot."""
    traces = sum(p.traces for p in cache._plans.values())
    calls = sum(p.calls for p in cache._plans.values())
    return cache.hits, cache.misses, traces, calls


def _record_cache_delta(reg, cache: PlanCache, before) -> None:
    """Book what the cache did during one executor call under plan_cache/."""
    if reg is None or not reg.enabled:
        return
    h, m, t, c = _cache_stats(cache)
    h0, m0, t0, c0 = before
    with reg.scope("plan_cache"):
        reg.inc("hits", h - h0)
        reg.inc("misses", m - m0)
        reg.inc("retraces", t - t0)
        reg.inc("calls", c - c0)


def _dispatch_plan(cache: PlanCache, design: str, interpret: bool):
    """The graph walkers' (spec, pass) -> plan closure, trace-span aware.

    With a :class:`repro.obs.trace.TraceCollector` active, every plan
    invocation is wrapped in a host-side dispatch span (the wall time jax
    spends entering the jitted executable — Pallas dispatch overhead).
    """
    col = obs_trace.get_active_trace()

    def plan(spec, pass_):
        p = cache.get(spec, pass_, design, interpret)
        if col is None:
            return p

        label = getattr(spec, "label", None)  # RegionSpec names its chain
        name = label or f"{type(spec).__name__}:{pass_}"
        cat = "fused" if label else "dispatch"

        def timed(j):
            with col.host_span(name, tid="dispatch", cat=cat):
                return p(j)

        return timed

    return plan


def _as_jax_f32(inputs: dict) -> dict:
    """Inputs as float32 jax arrays; device arrays pass through untouched.

    The identity check matters for step-level dispatch: ``jnp.asarray``
    with a dtype is not free even on an already-f32 device array, and a
    dozen per-step no-op conversions cost as much as a fused kernel.
    """
    import jax
    import jax.numpy as jnp

    out = {}
    for k, v in inputs.items():
        if not (isinstance(v, jax.Array) and v.dtype == jnp.float32):
            v = jnp.asarray(v, jnp.float32)
        out[k] = v
    return out


def _resolve_interpret(interpret):
    if interpret is not None:
        return bool(interpret)
    import jax

    return jax.default_backend() != "tpu"


def _fusion_for(program, *, fuse_updates: bool):
    """The program's memoized FusionPlan (region formation is per-program)."""
    plans = program.meta.setdefault("_fusion_plans", {})
    plan = plans.get(fuse_updates)
    if plan is None:
        from repro.lower import fuse as fuse_mod

        plan = fuse_mod.plan_fusion(program, fuse_updates=fuse_updates)
        plans[fuse_updates] = plan
    return plan


def _graph_fingerprint(graph):
    """Hashable identity of everything a step callable bakes in."""
    return (
        tuple(
            (n.name, n.spec, n.param, n.in_edge, n.out_edge, n.aux_edges)
            for n in graph.nodes
        ),
        graph.loss,
        graph.batch,
        graph.lr,
        graph.momentum,
        graph.input_edge,
        graph.label_edge,
        graph.logits_edge,
    )


def _step_plan(cache, graph, fusion, design, interpret, *, keep_grads,
               relu_outputs=False):
    """One jitted callable for the WHOLE fused train step.

    The fused walk still dispatches 5-ish plans per step; at millisecond
    step times that per-plan jit entry overhead dominates the kernels
    themselves. Caching the entire segment walk as a single plan — keyed by
    the graph fingerprint plus the fusion plan's segment tuple (RegionSpecs
    are frozen) — collapses a step to one dispatch, with the region
    pallas_calls inlined into the step executable at trace time. Only used
    when no TraceCollector is active: traces want the per-plan host spans.
    """
    segs = tuple(
        s.step if s.region is None else s.region for s in fusion.segments
    )
    key = (
        "train_step",
        _graph_fingerprint(graph),
        segs,
        keep_grads,
        relu_outputs,
        design,
        bool(interpret),
    )

    def build():
        plan = _dispatch_plan(cache, design, interpret)

        def raw(j):
            return _graph_step_local(
                graph, j, plan, graph.batch, keep_grads=keep_grads,
                fusion=fusion, relu_outputs=relu_outputs,
            )

        return raw

    return cache.get_fn(key, build)


def _record_fusion(reg, fusion) -> None:
    """Book what the fuser covered this step under fusion/."""
    if reg is None or not reg.enabled or fusion is None:
        return
    with reg.scope("fusion"):
        reg.inc("regions", fusion.n_regions)
        reg.inc("fallback_dispatches", len(fusion.fallback_steps))
        reg.inc("fused_commands", fusion.fused_commands)
        reg.inc(
            "unfused_commands",
            fusion.total_commands - fusion.fused_commands,
        )


def run_pallas(
    program: NtxProgram,
    inputs: dict,
    *,
    interpret: bool | None = None,
    cache: PlanCache | None = None,
    fuse: bool = True,
    relu_outputs: bool = False,
):
    """Execute the lowered layer through the cached Pallas plans.

    ``interpret=None`` picks the Pallas interpreter off-TPU (CPU tests) and
    the compiled kernel on TPU. Inputs may be numpy or ``jax.Array`` —
    device arrays pass straight through (no host round trip) — and outputs
    are ``jax.Array``s keyed like :func:`run_reference`'s output dict.
    Repeated calls on equal specs reuse one jitted executable from
    ``cache`` (default: the process-wide :data:`PLAN_CACHE`).

    Fused train-step programs execute as ONE cached jitted callable per
    step (the region kernels inline into it at trace time), so the warm
    path is a single dispatch — the executor analogue of the paper's
    "one offload per training step" goal.

    ``fuse`` (train-step programs only) routes the graph walk through the
    :mod:`repro.lower.fuse` region plan — whole fwd/bwd chains as single
    fused kernels — with per-node dispatch as the fallback for steps
    without a fusion rule. ``fuse=False`` is the escape hatch: the original
    one-plan-per-node walk, bit-for-bit the PR-4 behaviour.

    ``relu_outputs`` (train-step programs only) adds the output edge of
    every ReLU forward the walk runs outside a fused region to the outputs,
    keyed by edge name — what a gradient check needs to see which side of
    the kink each element took. It is part of the step's cache key.
    """
    interpret = _resolve_interpret(interpret)
    if cache is None:
        cache = PLAN_CACHE
    reg = obs.get_active()
    before = _cache_stats(cache) if reg is not None else None
    if program.meta.get("pass") == "train_step":
        if "mesh" in program.meta:
            out = _run_pallas_graph_mesh(
                program, inputs, interpret, cache, fuse=fuse,
                relu_outputs=relu_outputs,
            )
        else:
            out = _run_pallas_graph(
                program, inputs, interpret, cache, fuse=fuse,
                relu_outputs=relu_outputs,
            )
    else:
        spec = program.meta.get("spec")
        pass_ = program.meta.get("pass", "fwd")
        plan = _dispatch_plan(cache, program.design.name, interpret)(spec, pass_)
        out = plan(_as_jax_f32(inputs))
    if reg is not None:
        # The counters are the *program's* closed-form offload/DMA
        # arithmetic — what the NTX cube would execute for this step — not
        # a measurement of the jax backend that computed the numerics.
        obs.record_program(reg, program)
        _record_cache_delta(reg, cache, before)
    return out


def _run_pallas_graph(program, inputs, interpret: bool, cache, fuse=True,
                      relu_outputs=False):
    """Graph-driven Pallas execution of one whole-train-step program.

    Walks the :class:`repro.lower.graph.NetworkGraph` behind ``program`` in
    the same fwd → loss grad → dW/update/dX schedule the command stream
    encodes. With ``fuse`` (the default) the walk follows the program's
    :class:`repro.lower.fuse.FusionPlan`: contiguous fusable chains run as
    single region kernels, everything else through the cached per-node
    plans (per-image nodes key as :class:`BatchedSpec`). Outputs carry the
    program's output-region names — logits, ``d_<param>`` (when kept),
    ``<param>_new`` and ``v_<param>_new`` — so callers are
    executor-agnostic.
    """
    graph = program.meta["graph"]
    keep_grads = program.meta.get("keep_grads", True)
    j = _as_jax_f32(inputs)
    fusion = _fusion_for(program, fuse_updates=True) if fuse else None
    _record_fusion(obs.get_active(), fusion)
    if fusion is not None and obs_trace.get_active_trace() is None:
        step = _step_plan(cache, graph, fusion, program.design.name,
                          interpret, keep_grads=keep_grads,
                          relu_outputs=relu_outputs)
        return step(j)
    plan = _dispatch_plan(cache, program.design.name, interpret)
    return _graph_step_local(graph, j, plan, graph.batch,
                             keep_grads=keep_grads, fusion=fusion,
                             relu_outputs=relu_outputs)


def _relu_edges(graph, fusion) -> tuple[str, ...]:
    """Output edges of the ReLU forwards a walk runs outside fused regions
    (all of them without ``fusion``): the ``relu_outputs`` of a step."""
    ran = None if fusion is None else set(fusion.fallback_steps)
    return tuple(
        n.out_edge for n in graph.nodes
        if isinstance(n.spec, ReluSpec)
        and (ran is None or f"{n.name}:fwd" in ran)
    )


def _graph_step_local(graph, j, plan, B, *, keep_grads=True,
                      grad_reduce=None, batched=None, fusion=None,
                      relu_outputs=False):
    """One train step over ``B``-image arrays through cached per-node plans.

    ``B`` is the batch the arrays actually carry — the graph's full batch
    on the single-device path, the per-shard slice inside the mesh route's
    ``shard_map`` body (where ``grad_reduce`` is the cross-shard psum that
    realizes the gradient allreduce; the loss plan's 1/B_global scale makes
    the psum a batch mean). ``batched`` forces a leading batch axis on the
    activations even at ``B == 1`` — a mesh shard of one image still
    carries its axis so the out-spec concatenation works. The walk mirrors
    the command stream's fwd → loss grad → dW/update/dX schedule exactly;
    with ``fusion`` set it follows the fusion plan's segments instead —
    the same schedule, chains collapsed into region dispatches.
    ``relu_outputs`` adds the :func:`_relu_edges` activations to the outputs.
    """
    reduce = grad_reduce or (lambda g: g)
    batched = (B > 1) if batched is None else batched
    if fusion is not None:
        outs, env = _walk_fused(graph, j, plan, B, fusion,
                                keep_grads=keep_grads, reduce=reduce,
                                batched=batched)
        if relu_outputs:
            outs.update((e, env[e]) for e in _relu_edges(graph, fusion))
        return outs

    def bspec(spec):
        return BatchedSpec(spec, B) if batched else spec

    # forward
    acts = {graph.input_edge: j[graph.input_edge]}
    for node in graph.nodes:
        s, a = node.spec, acts[node.in_edge]
        if isinstance(s, Conv2dSpec):
            y = plan(bspec(s), "fwd")({"x": a, "w": j[node.param]})["y"]
        elif isinstance(s, MatmulSpec):
            y = plan(s, "fwd")({"a": a, "b": j[node.param]})["c"]
        elif isinstance(s, BiasSpec):
            y = plan(s, "fwd")({"x": a.reshape(-1, s.c), "b": j[node.param]})
            y = y["y"].reshape(a.shape)
        elif isinstance(s, ReluSpec):
            whole = ReluSpec((B,) + tuple(s.shape)) if batched else s
            y = plan(whole, "fwd")({"x": a})["y"]
        elif isinstance(s, MaxPool2dSpec):
            y = plan(bspec(s), "fwd")({"x": a})["y"]
        elif isinstance(s, FlattenSpec):
            y = a.reshape((B, s.size) if batched else (s.size,))
        elif isinstance(s, AttentionSpec):
            # per-sequence node over token-row activations (rows = B*S)
            xb = a.reshape(-1, s.seq, 3 * s.d)
            y = plan(BatchedSpec(s, xb.shape[0]), "fwd")({"x": xb})["y"]
            y = y.reshape(-1, s.d)
        elif isinstance(s, LayerNormSpec):
            y = plan(s, "fwd")({"x": a, "w": j[node.param]})["y"]
        elif isinstance(s, ResidualAddSpec):
            y = plan(s, "fwd")(
                {"x": a, "x2": acts[node.aux_edges[0]]}
            )["y"]
        elif isinstance(s, EmbeddingSpec):
            y = plan(s, "fwd")({"x": a, "w": j[node.param]})["y"]
        elif isinstance(s, PosEmbedSpec):
            # -1, not s.batch: mesh shards walk with a local batch
            xb = a.reshape(-1, s.seq, s.d)
            y = plan(s, "fwd")({"x": xb, "w": j[node.param]})["y"]
            y = y.reshape(-1, s.d)
        else:
            raise TypeError(f"no graph route for {type(s).__name__}")
        acts[node.out_edge] = y

    logits = acts[graph.logits_edge]
    outs = {graph.logits_edge: logits}
    if relu_outputs:
        outs.update((e, acts[e]) for e in _relu_edges(graph, None))

    # loss gradient seeds the per-edge gradient map; DAG fan-out edges
    # accumulate one contribution per consumer (matching the compiled
    # program's partial + accumulate-step schedule)
    grads = {graph.logits_edge: plan(graph.loss, "dx")(
        {"z": logits, "onehot": j[graph.label_edge]}
    )["dz"]}

    def add_grad(edge, v):
        grads[edge] = grads[edge] + v if edge in grads else v

    # backward: dW -> update -> dX per node, in reverse
    for node in reversed(graph.nodes):
        s, a_in = node.spec, acts[node.in_edge]
        g = grads[node.out_edge]
        if node.param is not None:
            p = node.param
            if isinstance(s, Conv2dSpec):
                dw = plan(bspec(s), "dw")({"x": a_in, "dy": g})["dw"]
            elif isinstance(s, MatmulSpec):
                dw = plan(s, "dw")({"a": a_in, "dy": g})["dw"]
            elif isinstance(s, BiasSpec):
                dw = plan(s, "dw")({"dy": g.reshape(-1, s.c)})["db"]
            elif isinstance(s, (LayerNormSpec, EmbeddingSpec)):
                dw = plan(s, "dw")({"x": a_in, "dy": g})["dw"]
            elif isinstance(s, PosEmbedSpec):
                dw = plan(s, "dw")(
                    {"dy": g.reshape(-1, s.seq, s.d)}
                )["dw"]
            else:
                raise TypeError(f"no dW route for {type(s).__name__}")
            dw = reduce(dw)
            if keep_grads:
                outs[f"d_{p}"] = dw
            u_spec = SgdUpdateSpec(
                n=dw.size, lr=graph.lr, momentum=graph.momentum
            )
            u_in = {"w": j[p].reshape(-1), "dw": dw.reshape(-1)}
            if graph.momentum:
                u_in["v"] = j[f"v_{p}"].reshape(-1)
            u = plan(u_spec, "upd")(u_in)
            outs[f"{p}_new"] = u["w_new"].reshape(j[p].shape)
            if graph.momentum:
                outs[f"v_{p}_new"] = u["v_new"].reshape(j[p].shape)
        if node.in_edge == graph.input_edge:
            continue
        if isinstance(s, Conv2dSpec):
            gx = plan(bspec(s), "dx")({"dy": g, "w": j[node.param]})["dx"]
        elif isinstance(s, MatmulSpec):
            gx = plan(s, "dx")({"dy": g, "b": j[node.param]})["dx"]
        elif isinstance(s, ReluSpec):
            whole = ReluSpec((B,) + tuple(s.shape)) if batched else s
            gx = plan(whole, "dx")({"x": a_in, "dy": g})["dx"]
        elif isinstance(s, MaxPool2dSpec):
            gx = plan(bspec(s), "dx")({"x": a_in, "dy": g})["dx"]
        elif isinstance(s, AttentionSpec):
            xb = a_in.reshape(-1, s.seq, 3 * s.d)
            gx = plan(BatchedSpec(s, xb.shape[0]), "dx")(
                {"x": xb, "dy": g.reshape(-1, s.seq, s.d)}
            )["dx"].reshape(a_in.shape)
        elif isinstance(s, LayerNormSpec):
            gx = plan(s, "dx")(
                {"x": a_in, "w": j[node.param], "dy": g}
            )["dx"]
        elif isinstance(s, ResidualAddSpec):
            gx = plan(s, "dx")({"dy": g})["dx"]
            add_grad(node.aux_edges[0], gx)
        elif isinstance(s, PosEmbedSpec):
            gx = plan(s, "dx")(
                {"dy": g.reshape(-1, s.seq, s.d)}
            )["dx"].reshape(-1, s.d)
        elif isinstance(s, (FlattenSpec, BiasSpec)):
            gx = g.reshape(a_in.shape)
        else:
            raise TypeError(f"no dX route for {type(s).__name__}")
        add_grad(node.in_edge, gx)
    return outs


def _walk_fused(graph, j, plan, B, fusion, *, keep_grads, reduce, batched):
    """The fused segment walk: region kernels + per-node fallback steps.
    Returns the step's outputs and the walk's edge environment.

    Activations and activation gradients live in ``env`` keyed by edge
    name (gradient of edge ``e`` is ``d_<e>``) so region dispatches and
    fallback steps compose in any interleaving the fusion plan produced.
    Regions containing fused SGD updates require ``reduce`` to be the
    identity — the fuser only emits them on the single-device path.
    """
    import dataclasses

    nodes = {n.name: n for n in graph.nodes}
    env = {graph.input_edge: j[graph.input_edge]}
    outs: dict = {}

    def bspec(spec):
        return BatchedSpec(spec, B) if batched else spec

    def add_grad(edge, v):
        key = f"d_{edge}"
        env[key] = env[key] + v if key in env else v

    def exec_step(key):
        name, pass_ = key.split(":")
        if pass_ == "acc":
            # fan-out accumulate: the jax walk sums contributions into
            # d_<edge> as each consumer's dx lands, so by the time the
            # compiled schedule reaches the acc step there is nothing
            # left to do
            return
        if name == "loss":
            env[f"d_{graph.logits_edge}"] = plan(graph.loss, "dx")(
                {"z": env[graph.logits_edge], "onehot": j[graph.label_edge]}
            )["dz"]
            return
        node = nodes[name]
        s = node.spec
        if pass_ == "fwd":
            a = env[node.in_edge]
            if isinstance(s, Conv2dSpec):
                y = plan(bspec(s), "fwd")({"x": a, "w": j[node.param]})["y"]
            elif isinstance(s, MatmulSpec):
                y = plan(s, "fwd")({"a": a, "b": j[node.param]})["c"]
            elif isinstance(s, BiasSpec):
                y = plan(s, "fwd")(
                    {"x": a.reshape(-1, s.c), "b": j[node.param]}
                )["y"].reshape(a.shape)
            elif isinstance(s, ReluSpec):
                whole = ReluSpec((B,) + tuple(s.shape)) if batched else s
                y = plan(whole, "fwd")({"x": a})["y"]
            elif isinstance(s, MaxPool2dSpec):
                y = plan(bspec(s), "fwd")({"x": a})["y"]
            elif isinstance(s, FlattenSpec):
                y = a.reshape((B, s.size) if batched else (s.size,))
            elif isinstance(s, AttentionSpec):
                xb = a.reshape(-1, s.seq, 3 * s.d)
                y = plan(BatchedSpec(s, xb.shape[0]), "fwd")({"x": xb})["y"]
                y = y.reshape(-1, s.d)
            elif isinstance(s, LayerNormSpec):
                y = plan(s, "fwd")({"x": a, "w": j[node.param]})["y"]
            elif isinstance(s, ResidualAddSpec):
                y = plan(s, "fwd")(
                    {"x": a, "x2": env[node.aux_edges[0]]}
                )["y"]
            elif isinstance(s, EmbeddingSpec):
                y = plan(s, "fwd")({"x": a, "w": j[node.param]})["y"]
            elif isinstance(s, PosEmbedSpec):
                # -1, not s.batch: mesh shards walk with a local batch
                xb = a.reshape(-1, s.seq, s.d)
                y = plan(s, "fwd")({"x": xb, "w": j[node.param]})["y"]
                y = y.reshape(-1, s.d)
            else:
                raise TypeError(f"no graph route for {type(s).__name__}")
            env[node.out_edge] = y
        elif pass_ == "dw":
            g = env[f"d_{node.out_edge}"]
            if isinstance(s, Conv2dSpec):
                dw = plan(bspec(s), "dw")(
                    {"x": env[node.in_edge], "dy": g}
                )["dw"]
            elif isinstance(s, MatmulSpec):
                dw = plan(s, "dw")({"a": env[node.in_edge], "dy": g})["dw"]
            elif isinstance(s, BiasSpec):
                dw = plan(s, "dw")({"dy": g.reshape(-1, s.c)})["db"]
            elif isinstance(s, (LayerNormSpec, EmbeddingSpec)):
                dw = plan(s, "dw")({"x": env[node.in_edge], "dy": g})["dw"]
            elif isinstance(s, PosEmbedSpec):
                dw = plan(s, "dw")(
                    {"dy": g.reshape(-1, s.seq, s.d)}
                )["dw"]
            else:
                raise TypeError(f"no dW route for {type(s).__name__}")
            dw = reduce(dw)
            env[f"d_{node.param}"] = dw
            if keep_grads:
                outs[f"d_{node.param}"] = dw
        elif pass_ == "upd":
            p = node.param
            dw = env[f"d_{p}"]
            u_spec = SgdUpdateSpec(
                n=dw.size, lr=graph.lr, momentum=graph.momentum
            )
            u_in = {"w": j[p].reshape(-1), "dw": dw.reshape(-1)}
            if graph.momentum:
                u_in["v"] = j[f"v_{p}"].reshape(-1)
            u = plan(u_spec, "upd")(u_in)
            outs[f"{p}_new"] = u["w_new"].reshape(j[p].shape)
            if graph.momentum:
                outs[f"v_{p}_new"] = u["v_new"].reshape(j[p].shape)
        else:  # dx
            g = env[f"d_{node.out_edge}"]
            if isinstance(s, Conv2dSpec):
                gx = plan(bspec(s), "dx")({"dy": g, "w": j[node.param]})["dx"]
            elif isinstance(s, MatmulSpec):
                gx = plan(s, "dx")({"dy": g, "b": j[node.param]})["dx"]
            elif isinstance(s, ReluSpec):
                whole = ReluSpec((B,) + tuple(s.shape)) if batched else s
                gx = plan(whole, "dx")(
                    {"x": env[node.in_edge], "dy": g}
                )["dx"]
            elif isinstance(s, MaxPool2dSpec):
                gx = plan(bspec(s), "dx")(
                    {"x": env[node.in_edge], "dy": g}
                )["dx"]
            elif isinstance(s, AttentionSpec):
                a_in = env[node.in_edge]
                xb = a_in.reshape(-1, s.seq, 3 * s.d)
                gx = plan(BatchedSpec(s, xb.shape[0]), "dx")(
                    {"x": xb, "dy": g.reshape(-1, s.seq, s.d)}
                )["dx"].reshape(a_in.shape)
            elif isinstance(s, LayerNormSpec):
                gx = plan(s, "dx")(
                    {"x": env[node.in_edge], "w": j[node.param], "dy": g}
                )["dx"]
            elif isinstance(s, ResidualAddSpec):
                gx = plan(s, "dx")({"dy": g})["dx"]
                add_grad(node.aux_edges[0], gx)
            elif isinstance(s, PosEmbedSpec):
                gx = plan(s, "dx")(
                    {"dy": g.reshape(-1, s.seq, s.d)}
                )["dx"].reshape(-1, s.d)
            elif isinstance(s, FlattenSpec):
                shape = tuple(s.in_shape)
                gx = g.reshape((B,) + shape if batched else shape)
            else:  # BiasSpec dx: shape-preserving passthrough
                gx = g.reshape(env[node.in_edge].shape)
            add_grad(node.in_edge, gx)

    for seg in fusion.segments:
        if seg.region is None:
            exec_step(seg.step)
            continue
        region = seg.region
        if region.batch != B:
            region = dataclasses.replace(region, batch=B)
        ins = {}
        for name, is_b in region.inputs:
            v = env[name] if name in env else j[name]
            ins[name] = v[None] if (is_b and not batched) else v
        ro = plan(region, "region")(ins)
        for name, kind in region.outputs:
            v = ro[name]
            if kind == "batched":
                env[name] = v if batched else v[0]
            elif name.startswith("d_"):
                dw = reduce(v)
                env[name] = dw
                if keep_grads:
                    outs[name] = dw
            else:  # <param>_new / v_<param>_new epilogue results
                outs[name] = v
    outs[graph.logits_edge] = env[graph.logits_edge]
    return outs, env


def _run_pallas_graph_mesh(program, inputs, interpret: bool, cache,
                           fuse=True, relu_outputs=False):
    """Data-parallel execution of a mesh-sharded train-step program.

    The batch shards over a ``(pod, data)`` jax device mesh shaped like the
    HMC mesh (the same DP-axis convention as :mod:`repro.parallel.sharding`)
    via ``shard_map``; each shard walks the graph on its slice through the
    shared :class:`PlanCache`, and the gradient allreduce epilogue is a
    cross-shard ``psum`` — a batch *mean* because the loss plan already
    scales by 1 / B_global. Updated weights come back replicated, exactly
    like the allgather of the command-level epilogue. With fewer jax
    devices than HMCs, compiled kernels raise before any kernel runs; only
    the interpreter (CPU tests) walks the mesh program unsharded on the
    full batch — the same numerics, minus the parallelism.

    Elastically re-sharded programs (``mesh_meta["alive"]`` set by
    :func:`repro.lower.mesh.reshard_training_step`) re-enter ``shard_map``
    over a SHRUNKEN ``(1, n_alive)`` jax mesh — the survivors' batch
    shards, with the psum spanning only the shrunken mesh. When the batch
    no longer divides the survivor count (uneven re-chunking), the
    program walks the full batch on one device (so does the interpreter
    when too few jax devices remain).

    2D-sharded programs (``mesh_meta["shard"] == "2d"``) run over a real
    2D jax mesh with ``("pipe", "data")`` axes shaped like the physical
    (pipeline rows x tensor/data columns) grid. The jax mesh computes the
    data-parallel *numerics* — batch sharded over both axes, gradient
    psum across the full mesh — which is exactly the arithmetic the
    2D command stream replays (identity-copy communication, disjoint
    output splits), so gradients match ``jax.grad`` bit-for-tolerance
    like the 1D path; the pipeline fill/drain and tensor-shard structure
    live in the command stream and the timing model
    (:func:`repro.runtime.mesh.time_mesh_step_2d`), not in XLA's
    schedule. ``fuse_updates=False`` already holds on this path: the
    cross-mesh psum must run between dW and the SGD update whether the
    columns are data- or tensor-sharded, so the fuser interaction is
    identical for both layouts.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import compat

    graph = program.meta["graph"]
    mesh_meta = program.meta["mesh"]
    rows, cols = mesh_meta["shape"]
    n = mesh_meta["n_hmcs"]
    alive = mesh_meta.get("alive")
    n_alive = len(alive) if alive is not None else n
    B = graph.batch
    keep_grads = program.meta.get("keep_grads", True)
    j = _as_jax_f32(inputs)
    plan = _dispatch_plan(cache, program.design.name, interpret)

    if jax.device_count() < n_alive and not interpret:
        raise RuntimeError(
            f"mesh program needs {n_alive} jax devices, found "
            f"{jax.device_count()}: compiled kernels never walk a mesh "
            f"program on one device (only the interpreter does)"
        )
    if jax.device_count() < n_alive or B % n_alive:
        fusion = _fusion_for(program, fuse_updates=True) if fuse else None
        _record_fusion(obs.get_active(), fusion)
        if fusion is not None and obs_trace.get_active_trace() is None:
            step = _step_plan(cache, graph, fusion, program.design.name,
                              interpret, keep_grads=keep_grads,
                              relu_outputs=relu_outputs)
            return step(j)
        return _graph_step_local(graph, j, plan, B, keep_grads=keep_grads,
                                 fusion=fusion, relu_outputs=relu_outputs)
    # inside shard_map the gradient psum must run between dW and the SGD
    # update, so regions keep the updates as per-node fallback dispatches
    fusion = _fusion_for(program, fuse_updates=False) if fuse else None
    _record_fusion(obs.get_active(), fusion)

    # 2D programs name the axes after their meaning (pipeline rows x
    # tensor/data columns); 1D keeps the (pod, data) convention of
    # repro.parallel.sharding. Either way both axes carry batch shards.
    dp_axes = (
        ("pipe", "data") if mesh_meta.get("shard") == "2d" else ("pod", "data")
    )
    # a degraded mesh no longer matches the physical (rows, cols) grid:
    # lay the survivors out along one axis of a shrunken jax mesh
    jax_shape = (rows, cols) if n_alive == n else (1, n_alive)
    mesh = compat.make_mesh(jax_shape, dp_axes)
    sharded_edges = {graph.input_edge, graph.label_edge}

    def batch_spec(name):
        return P(dp_axes) if name in sharded_edges else P()

    in_specs = ({k: batch_spec(k) for k in j},)
    out_specs = {graph.logits_edge: P(dp_axes)}
    for p in graph.param_shapes():
        out_specs[f"{p}_new"] = P()
        if keep_grads:
            out_specs[f"d_{p}"] = P()
        if graph.momentum:
            out_specs[f"v_{p}_new"] = P()
    if relu_outputs:
        # activations keep their batch shards, concatenated in batch order
        out_specs.update((e, P(dp_axes)) for e in _relu_edges(graph, fusion))

    def per_shard(shard_j):
        return _graph_step_local(
            graph, shard_j, plan, B // n_alive, keep_grads=keep_grads,
            grad_reduce=lambda g: jax.lax.psum(g, dp_axes), batched=True,
            fusion=fusion, relu_outputs=relu_outputs,
        )

    return compat.shard_map(
        per_shard, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=set(dp_axes), check_vma=False,
    )(j)
