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

import functools
from dataclasses import dataclass, replace
from typing import Callable

import numpy as np

from repro.core.ntx import ntx_execute
from repro.lower.fuse import RegionSpec, plan_fusion, unfused_plan
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
    memset/copy commands — no out-of-band padding happens here. A
    train-step program's outputs also carry ``graph.loss_rows_edge``: each
    logits row's cross-entropy, which the command stream leaves to the
    host, computed here in float64 from the logits and one-hot labels.
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
    outs = {
        r.name: mem[r.base : r.end].reshape(r.shape).copy()
        for r in program.regions_of_kind("output")
    }
    if program.meta.get("pass") == "train_step":
        graph = program.meta["graph"]
        outs[graph.loss_rows_edge] = _xent_rows(
            outs[graph.logits_edge], inputs[graph.label_edge])
    return outs


def _xent_rows(logits, onehot) -> np.ndarray:
    """float64 softmax cross-entropy of each logits row against its
    one-hot label row."""
    y = np.asarray(onehot, np.float64)
    z = np.asarray(logits, np.float64).reshape(-1, y.shape[-1])
    z = z - z.max(axis=1, keepdims=True)
    lse = np.log(np.exp(z).sum(axis=1))
    return lse - (z * y.reshape(z.shape)).sum(axis=1)


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


#: spec type -> its entry in the Pallas executor, one per node type:
#: {"plans": fn, "fwd" | "dw" | "dx": wiring} (see :func:`_route`)
_ROUTES: dict[type, dict[str, Callable]] = {}


def _route(spec_type: type, **wiring):
    """Decorator: register ``plans(spec, interpret)`` and ``wiring`` as
    ``spec_type``'s entry.

    ``plans`` maps each pass the type runs as one plan to its pure jax
    callable ``dict[str, Array] -> dict[str, Array]`` (what
    :class:`PlanCache` jits). ``fwd(wk, node, x)``, ``dw(wk, node, dy)`` and
    ``dx(wk, node, dy)`` wire a node into the walk ``wk`` (ports, reshapes,
    plan key) and return its output, parameter gradient and input
    gradient. The loss gradient and the SGD update belong to the walk.
    """

    def deco(plans):
        _ROUTES[spec_type] = {"plans": plans, **wiring}
        return plans

    return deco


class _Walk:
    """What one step walk hands the wiring: the step's inputs ``j``, the
    edge environment ``env`` (gradient of edge ``e`` at ``d_<e>``), and a
    leading batch axis of ``B`` on the activations when ``batched``."""

    def __init__(self, j, plan, B, batched):
        self.j, self.plan, self.B, self.batched = j, plan, B, batched
        self.env = {}

    def act(self, node):
        return self.env[node.in_edge]

    def param(self, node):
        return self.j[node.param]

    def per_image(self, spec):
        """The plan key of a per-image spec over the walk's batch."""
        return BatchedSpec(spec, self.B) if self.batched else spec

    def add_grad(self, edge, v):
        """Add one consumer's contribution to the gradient of ``edge``."""
        key = f"d_{edge}"
        self.env[key] = self.env[key] + v if key in self.env else v


@_route(
    MatmulSpec,
    fwd=lambda wk, n, x: wk.plan(n.spec, "fwd")({"a": x, "b": wk.param(n)})["c"],
    dw=lambda wk, n, dy: wk.plan(n.spec, "dw")({"a": wk.act(n), "dy": dy})["dw"],
    dx=lambda wk, n, dy: wk.plan(n.spec, "dx")({"dy": dy, "b": wk.param(n)})["dx"],
)
def _matmul_plans(spec: MatmulSpec, interpret: bool):
    from repro.kernels import streaming

    mm = functools.partial(streaming.streaming_matmul, interpret=interpret)
    return {
        "fwd": lambda j: {"c": mm(j["a"], j["b"])},
        "dw": lambda j: {"dw": mm(j["a"].T, j["dy"])},
        "dx": lambda j: {"dx": mm(j["dy"], j["b"].T)},
    }


def _conv_plans(spec: Conv2dSpec, interpret: bool):
    """A conv's passes over a batch of images (N, H, W, C) in the streaming
    kernel. The images fold into the matmul rows, so dW comes out summed
    over the batch (the images join the contraction, as in the fused region).
    """
    import jax.numpy as jnp

    from repro.core import conv_decomp
    from repro.kernels import streaming

    s, p = spec.stride, spec.padding
    conv = functools.partial(streaming.streaming_conv2d, interpret=interpret)

    # dW = cols(x)^T @ dy: the same im2col the forward kernel streams,
    # with the (n*oh*ow) output pixels as the contraction dim.
    def dw(j):
        x = j["x"]
        if p:
            x = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
        oh, ow = spec.out_h, spec.out_w
        taps = [x[:, dh : dh + oh * s : s, dw_ : dw_ + ow * s : s, :]
                for dh in range(spec.kh) for dw_ in range(spec.kw)]
        cols = jnp.concatenate(taps, axis=-1).reshape(-1, spec.kh * spec.kw * spec.cin)
        dyf = j["dy"].reshape(-1, spec.cout)
        dw_flat = streaming.streaming_matmul(cols.T, dyf, interpret=interpret)
        return {"dw": dw_flat.reshape(spec.kh, spec.kw, spec.cin, spec.cout)}

    # The §3.2 phase decomposition with the dense per-phase conv
    # routed through the streaming Pallas kernel.
    def conv_fn(dy, w_ab, pads):
        ph, pw = pads
        dyp = jnp.pad(dy, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
        return conv(dyp, w_ab, stride=1, padding=0)

    return {
        "fwd": lambda j: {"y": conv(j["x"], j["w"], stride=s, padding=p)},
        "dw": dw,
        "dx": lambda j: {"dx": conv_decomp.conv2d_input_grad_decomposed(
            j["dy"], j["w"], s, (spec.in_h, spec.in_w), p, conv_fn=conv_fn)},
    }


@_route(
    Conv2dSpec,
    fwd=lambda wk, n, x: wk.plan(wk.per_image(n.spec), "fwd")({"x": x, "w": wk.param(n)})["y"],
    dw=lambda wk, n, dy: wk.plan(wk.per_image(n.spec), "dw")({"x": wk.act(n), "dy": dy})["dw"],
    dx=lambda wk, n, dy: wk.plan(wk.per_image(n.spec), "dx")({"dy": dy, "w": wk.param(n)})["dx"],
)
def _conv_one_image_plans(spec: Conv2dSpec, interpret: bool):
    # one image: lend it a batch axis of one and take it back off
    # (dW has no batch axis to remove)
    def one_image(conv):
        def run(j):
            out = conv({k: v if k == "w" else v[None] for k, v in j.items()})
            return {k: v if k == "dw" else v[0] for k, v in out.items()}

        return run

    return {p: one_image(f) for p, f in _conv_plans(spec, interpret).items()}


@_route(
    MaxPool2dSpec,
    fwd=lambda wk, n, x: wk.plan(wk.per_image(n.spec), "fwd")({"x": x})["y"],
    dx=lambda wk, n, dy: wk.plan(wk.per_image(n.spec), "dx")({"x": wk.act(n), "dy": dy})["dx"],
)
def _maxpool_plans(spec: MaxPool2dSpec, interpret: bool):
    import jax
    import jax.numpy as jnp

    w, s = spec.window, spec.stride

    def pool_fwd(x):
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (w, w, 1), (s, s, 1), "VALID"
        )

    def pool_dx(j):
        _, vjp = jax.vjp(pool_fwd, j["x"])
        return {"dx": vjp(j["dy"])[0]}

    return {"fwd": lambda j: {"y": pool_fwd(j["x"])}, "dx": pool_dx}


def _relu_whole(wk, spec):
    """A ReLU runs once over the walk's whole batch."""
    return ReluSpec((wk.B,) + tuple(spec.shape)) if wk.batched else spec


@_route(
    ReluSpec,
    fwd=lambda wk, n, x: wk.plan(_relu_whole(wk, n.spec), "fwd")({"x": x})["y"],
    dx=lambda wk, n, dy: wk.plan(_relu_whole(wk, n.spec), "dx")({"x": wk.act(n), "dy": dy})["dx"],
)
def _relu_plans(spec: ReluSpec, interpret: bool):
    import jax.numpy as jnp

    return {
        "fwd": lambda j: {"y": jnp.maximum(j["x"], 0.0)},
        # the sign/select mask pattern of the lowering rule, in jnp
        "dx": lambda j: {"dx": jnp.where(j["x"] > 0.0, j["dy"], 0.0)},
    }


@_route(
    BiasSpec,
    fwd=lambda wk, n, x: wk.plan(n.spec, "fwd")(
        {"x": x.reshape(-1, n.spec.c), "b": wk.param(n)}
    )["y"].reshape(x.shape),
    dw=lambda wk, n, dy: wk.plan(n.spec, "dw")({"dy": dy.reshape(-1, n.spec.c)})["db"],
    # the walk passes the gradient through: no dispatch
    dx=lambda wk, n, dy: dy.reshape(wk.act(n).shape),
)
def _bias_plans(spec: BiasSpec, interpret: bool):
    return {
        "fwd": lambda j: {"y": j["x"] + j["b"][None, :]},
        "dw": lambda j: {"db": j["dy"].sum(axis=0)},
        "dx": lambda j: {"dx": j["dy"]},
    }


@_route(
    FlattenSpec,
    fwd=lambda wk, n, x: x.reshape((wk.B, n.spec.size) if wk.batched else (n.spec.size,)),
    dx=lambda wk, n, dy: dy.reshape(
        (wk.B, *n.spec.in_shape) if wk.batched else tuple(n.spec.in_shape)
    ),
)
def _flatten_plans(spec: FlattenSpec, interpret: bool):
    return {}  # a zero-copy view: the walk reshapes, no plan runs


@_route(SoftmaxXentSpec)
def _xent_plans(spec: SoftmaxXentSpec, interpret: bool):
    from repro.kernels.fused import softmax_xent

    def xent_dx(j):
        dz, rows = softmax_xent(j["z"], j["onehot"], spec.batch)
        return {"dz": dz, "rows": rows.reshape(-1)}

    return {"dx": xent_dx}


@_route(SgdUpdateSpec)
def _sgd_plans(spec: SgdUpdateSpec, interpret: bool):
    lr, mu = spec.lr, spec.momentum
    if not mu:
        return {"upd": lambda j: {"w_new": j["w"] - lr * j["dw"]}}

    def upd_mom(j):
        v_new = mu * j["v"] + j["dw"]
        return {"v_new": v_new, "w_new": j["w"] - lr * v_new}

    return {"upd": upd_mom}


def _attention_fwd(wk, n, x):
    # per-sequence node over token-row activations (rows = B*S)
    s = n.spec
    xb = x.reshape(-1, s.seq, 3 * s.d)
    y = wk.plan(BatchedSpec(s, xb.shape[0]), "fwd")({"x": xb})["y"]
    return y.reshape(-1, s.d)


def _attention_dx(wk, n, dy):
    s, a_in = n.spec, wk.act(n)
    xb = a_in.reshape(-1, s.seq, 3 * s.d)
    return wk.plan(BatchedSpec(s, xb.shape[0]), "dx")(
        {"x": xb, "dy": dy.reshape(-1, s.seq, s.d)}
    )["dx"].reshape(a_in.shape)


@_route(AttentionSpec, fwd=_attention_fwd, dx=_attention_dx)
def _attention_plans(spec: AttentionSpec, interpret: bool):
    import jax
    import jax.numpy as jnp

    S, H, Dh = spec.seq, spec.n_heads, spec.head_dim
    D = H * Dh

    def attn_one(x):  # (S, 3D) qkv -> (S, D) context, causal
        q = x[:, :D].reshape(S, H, Dh).transpose(1, 0, 2)
        k = x[:, D : 2 * D].reshape(S, H, Dh).transpose(1, 0, 2)
        v = x[:, 2 * D :].reshape(S, H, Dh).transpose(1, 0, 2)
        # full fp32 contractions: the TPU default rounds f32 to bf16
        hi = jax.lax.Precision.HIGHEST
        sc = jnp.einsum("hid,hjd->hij", q, k, precision=hi) * spec.scale
        mask = jnp.where(jnp.tril(jnp.ones((S, S), x.dtype)) > 0, 0.0, -1e9)
        p = jax.nn.softmax(sc + mask[None], axis=-1)
        ctx = jnp.einsum("hij,hjd->hid", p, v, precision=hi)
        return ctx.transpose(1, 0, 2).reshape(S, D)

    def attn_dx(j):
        _, vjp = jax.vjp(attn_one, j["x"])
        return {"dx": vjp(j["dy"])[0]}

    return {"fwd": lambda j: {"y": attn_one(j["x"])}, "dx": attn_dx}


# the wiring layernorm and embedding share: input ``x``, parameter ``w``
def _fwd_x_w(wk, n, x):
    return wk.plan(n.spec, "fwd")({"x": x, "w": wk.param(n)})["y"]


def _dw_x_dy(wk, n, dy):
    return wk.plan(n.spec, "dw")({"x": wk.act(n), "dy": dy})["dw"]


@_route(
    LayerNormSpec,
    fwd=_fwd_x_w,
    dw=_dw_x_dy,
    dx=lambda wk, n, dy: wk.plan(n.spec, "dx")({"x": wk.act(n), "w": wk.param(n), "dy": dy})["dx"],
)
def _layernorm_plans(spec: LayerNormSpec, interpret: bool):
    import jax
    import jax.numpy as jnp

    eps = spec.eps

    def ln_xhat(j):
        mu = jnp.mean(j["x"], axis=-1, keepdims=True)
        var = jnp.mean((j["x"] - mu) ** 2, axis=-1, keepdims=True)
        return (j["x"] - mu) * jax.lax.rsqrt(var + eps)

    def ln_dx(j):
        xhat = ln_xhat(j)
        mu = jnp.mean(j["x"], axis=-1, keepdims=True)
        var = jnp.mean((j["x"] - mu) ** 2, axis=-1, keepdims=True)
        dyg = j["dy"] * j["w"][0]
        m1 = jnp.mean(dyg, axis=-1, keepdims=True)
        m2 = jnp.mean(dyg * xhat, axis=-1, keepdims=True)
        return {"dx": (dyg - m1 - xhat * m2) * jax.lax.rsqrt(var + eps)}

    return {
        "fwd": lambda j: {"y": ln_xhat(j) * j["w"][0] + j["w"][1]},
        "dw": lambda j: {
            "dw": jnp.stack([(j["dy"] * ln_xhat(j)).sum(axis=0), j["dy"].sum(axis=0)])
        },
        "dx": ln_dx,
    }


def _residual_dx(wk, n, dy):
    gx = wk.plan(n.spec, "dx")({"dy": dy})["dx"]
    wk.add_grad(n.aux_edges[0], gx)  # the second summand's gradient
    return gx


@_route(
    ResidualAddSpec,
    fwd=lambda wk, n, x: wk.plan(n.spec, "fwd")({"x": x, "x2": wk.env[n.aux_edges[0]]})["y"],
    dx=_residual_dx,
)
def _residual_plans(spec: ResidualAddSpec, interpret: bool):
    return {
        "fwd": lambda j: {"y": j["x"] + j["x2"]},
        "dx": lambda j: {"dx": j["dy"]},
    }


@_route(EmbeddingSpec, fwd=_fwd_x_w, dw=_dw_x_dy)
def _embedding_plans(spec: EmbeddingSpec, interpret: bool):
    from repro.kernels import streaming

    mm = functools.partial(streaming.streaming_matmul, interpret=interpret)
    return {
        "fwd": lambda j: {"y": mm(j["x"], j["w"])},
        "dw": lambda j: {"dw": mm(j["x"].T, j["dy"])},
    }


def _posembed_fwd(wk, n, x):
    # -1, not s.batch: mesh shards walk with a local batch
    s = n.spec
    xb = x.reshape(-1, s.seq, s.d)
    return wk.plan(s, "fwd")({"x": xb, "w": wk.param(n)})["y"].reshape(-1, s.d)


@_route(
    PosEmbedSpec,
    fwd=_posembed_fwd,
    dw=lambda wk, n, dy: wk.plan(n.spec, "dw")({"dy": dy.reshape(-1, n.spec.seq, n.spec.d)})["dw"],
    dx=lambda wk, n, dy: wk.plan(n.spec, "dx")(
        {"dy": dy.reshape(-1, n.spec.seq, n.spec.d)}
    )["dx"].reshape(-1, n.spec.d),
)
def _posembed_plans(spec: PosEmbedSpec, interpret: bool):
    return {
        "fwd": lambda j: {"y": j["x"] + j["w"][None]},
        "dw": lambda j: {"dw": j["dy"].sum(axis=0)},
        "dx": lambda j: {"dx": j["dy"]},
    }


def _plan_callable(spec, pass_: str, interpret: bool):
    """Pure jax function dict[str, Array] -> dict[str, Array] for one plan.

    Shapes/strides are baked in from ``spec`` (hashable frozen dataclasses),
    so one callable serves every invocation of that (spec, pass) — this is
    what :class:`PlanCache` jits and keeps.
    """
    import jax

    if isinstance(spec, RegionSpec):
        # one fused kernel for a whole region chain; ``pass_`` is "region"
        from repro.kernels import fused

        return fused.build_region_callable(spec, interpret=interpret)

    if isinstance(spec, BatchedSpec):
        if isinstance(spec.spec, Conv2dSpec):
            # the images fold into the streamed matmul rows: a vmap would
            # hand the ANY-space streaming kernel a squeezed batch block,
            # which Mosaic refuses
            conv = _conv_plans(spec.spec, interpret).get(pass_)
            if conv is not None:
                return conv
        inner = _plan_callable(spec.spec, pass_, interpret)

        def batched(j):
            axes = {k: (None if k in ("w", "b") else 0) for k in j}
            return jax.vmap(inner, in_axes=(axes,))(j)

        return batched

    plans = _ROUTES.get(type(spec), {}).get("plans")
    fn = None if plans is None else plans(spec, interpret).get(pass_)
    if fn is None:
        raise TypeError(f"no Pallas route for spec {type(spec).__name__} pass {pass_!r}")
    return fn


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


def _dispatch_plan(cache: PlanCache, design: str, interpret: bool, col=None):
    """The graph walk's (spec, pass) -> plan closure.

    With ``col`` (a :class:`repro.obs.trace.TraceCollector`), every plan
    invocation is wrapped in a host-side dispatch span (the wall time jax
    spends entering the jitted executable — Pallas dispatch overhead).
    Pass it only where plans are dispatched one by one from the host:
    inside a jitted step or a ``shard_map`` body a plan call is traced,
    not run, and its span would time the tracing.
    """

    def plan(spec, pass_):
        p = cache.get(spec, pass_, design, interpret)
        if col is None:
            return p

        name = f"{type(spec).__name__}:{pass_}"

        def timed(j):
            with col.host_span(name, tid="dispatch", cat="dispatch"):
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
        plan = plan_fusion(program, fuse_updates=fuse_updates)
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

    At millisecond step times the jit entry overhead of dispatching plan
    by plan dominates the kernels. Caching the entire segment walk as one
    plan — keyed by the graph fingerprint plus the fusion plan's segments
    (RegionSpecs are frozen) — collapses a step to one dispatch, with every
    plan inlined into the step executable at trace time. A TraceCollector
    sees the step as one dispatch (``train_graph``'s ``dispatch`` span);
    the executable is the same with or without one.
    """
    segs = tuple(s.step if s.region is None else s.region for s in fusion.segments)
    key = ("train_step", _graph_fingerprint(graph), segs, keep_grads,
           relu_outputs, design, bool(interpret))

    def build():
        plan = _dispatch_plan(cache, design, interpret)

        def raw(j):
            return _graph_step_local(
                graph, j, plan, graph.batch, fusion, keep_grads=keep_grads,
                relu_outputs=relu_outputs,
            )

        return raw

    return cache.get_fn(key, build)


def _record_fusion(reg, fusion) -> None:
    """Book what the fuser covered this step under fusion/."""
    if reg is None or not reg.enabled:
        return
    with reg.scope("fusion"):
        reg.inc("regions", fusion.n_regions)
        reg.inc("fallback_dispatches", len(fusion.fallback_steps))
        reg.inc("fused_commands", fusion.fused_commands)
        reg.inc("unfused_commands", fusion.total_commands - fusion.fused_commands)


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

    ``fuse`` (train-step programs only) runs the step as ONE cached jitted
    callable: the kernels of the :mod:`repro.lower.fuse` region plan and
    the per-node plans of the steps without a fusion rule inline into it
    at trace time, so the warm path is a single dispatch (the paper's "one
    offload per training step"). ``fuse=False`` is the escape hatch: the
    same graph walk over a plan with no regions, every node pass's cached
    plan dispatched from the host — the reference the region kernels are
    tested against. A train step's outputs also carry
    ``graph.loss_rows_edge``: each logits row's float32 cross-entropy,
    computed beside the loss gradient on every route, so the host reads
    one float per row and never the logits.

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
        run = _run_pallas_graph_mesh if "mesh" in program.meta else _run_pallas_graph
        out = run(program, inputs, interpret, cache, fuse=fuse, relu_outputs=relu_outputs)
    else:
        spec = program.meta.get("spec")
        pass_ = program.meta.get("pass", "fwd")
        plan = _dispatch_plan(cache, program.design.name, interpret,
                              obs_trace.get_active_trace())(spec, pass_)
        out = plan(_as_jax_f32(inputs))
    if reg is not None:
        # The counters are the *program's* closed-form offload/DMA
        # arithmetic — what the NTX cube would execute for this step — not
        # a measurement of the jax backend that computed the numerics.
        obs.record_program(reg, program)
        _record_cache_delta(reg, cache, before)
    return out


def _walk_plan(program, fuse: bool, *, fuse_updates: bool):
    """The segments a step walk follows: the fuser's plan, booked under
    fusion/, or with ``fuse=False`` every step of the schedule as its own
    per-node segment."""
    if not fuse:
        return unfused_plan(program.meta["graph"])
    fusion = _fusion_for(program, fuse_updates=fuse_updates)
    _record_fusion(obs.get_active(), fusion)
    return fusion


def _run_pallas_graph(program, inputs, interpret: bool, cache, fuse=True,
                      relu_outputs=False):
    """Walk the graph behind one whole-train-step program on one device.

    With ``fuse`` the walk (:func:`_graph_step_local`) follows the
    program's :class:`repro.lower.fuse.FusionPlan` as one cached jitted
    step; ``fuse=False`` dispatches every node pass's plan from the host.
    Outputs carry the program's output-region names — logits,
    ``d_<param>`` (when kept), ``<param>_new`` and ``v_<param>_new`` — plus
    the loss rows, like :func:`run_reference`'s.
    """
    graph = program.meta["graph"]
    keep_grads = program.meta.get("keep_grads", True)
    design = program.design.name
    j = _as_jax_f32(inputs)
    fusion = _walk_plan(program, fuse, fuse_updates=True)
    if fuse:
        step = _step_plan(cache, graph, fusion, design, interpret,
                          keep_grads=keep_grads, relu_outputs=relu_outputs)
        return step(j)
    plan = _dispatch_plan(cache, design, interpret, obs_trace.get_active_trace())
    return _graph_step_local(graph, j, plan, graph.batch, fusion,
                             keep_grads=keep_grads, relu_outputs=relu_outputs)


def _relu_edges(graph, fusion) -> tuple[str, ...]:
    """Output edges of the ReLU forwards a walk runs outside fused regions:
    the ``relu_outputs`` of a step."""
    ran = set(fusion.fallback_steps)
    return tuple(n.out_edge for n in graph.nodes
                 if isinstance(n.spec, ReluSpec) and f"{n.name}:fwd" in ran)


def _graph_step_local(graph, j, plan, B, fusion, *, keep_grads=True,
                      grad_reduce=None, batched=None, relu_outputs=False):
    """The executor's one graph walk: a train step over ``B``-image arrays.

    It follows ``fusion``'s segments in the command stream's fwd → loss
    grad → dW/update/dX order: a region segment is one fused kernel, a step
    segment one per-node plan wired by its node type's :data:`_ROUTES` entry.
    ``B`` is the batch the arrays carry — the graph's full batch on one
    device, the per-shard slice in the mesh route's ``shard_map`` body,
    where ``grad_reduce`` is the cross-shard psum (the loss plan's
    1/B_global scale makes it a batch mean; regions with fused updates
    exist only off the mesh, where it is the identity). ``batched`` keeps a
    leading batch axis at ``B == 1``, as a one-image mesh shard needs.
    ``relu_outputs`` adds the :func:`_relu_edges` activations to the
    outputs. Each segment runs under ``jax.named_scope("<node>:<pass>")``
    (a region under its label), so its operations carry that name in the
    compiled step.
    """
    import jax

    reduce = grad_reduce or (lambda g: g)
    wk = _Walk(j, plan, B, (B > 1) if batched is None else batched)
    env = wk.env
    env[graph.input_edge] = j[graph.input_edge]
    nodes = {n.name: n for n in graph.nodes}
    outs: dict = {}

    def run_step(key):
        name, pass_ = key.split(":")
        if pass_ == "acc":
            # fan-out accumulate: each consumer's dx already added its
            # contribution into d_<edge> as it landed
            return
        if name == "loss":
            loss = plan(graph.loss, "dx")(
                {"z": env[graph.logits_edge], "onehot": j[graph.label_edge]}
            )
            env[f"d_{graph.logits_edge}"] = loss["dz"]
            env[graph.loss_rows_edge] = loss["rows"]
            return
        node = nodes[name]
        if pass_ == "upd":
            p = node.param
            dw = env[f"d_{p}"]
            u_spec = SgdUpdateSpec(n=dw.size, lr=graph.lr, momentum=graph.momentum)
            u_in = {"w": j[p].reshape(-1), "dw": dw.reshape(-1)}
            if graph.momentum:
                u_in["v"] = j[f"v_{p}"].reshape(-1)
            u = plan(u_spec, "upd")(u_in)
            outs[f"{p}_new"] = u["w_new"].reshape(j[p].shape)
            if graph.momentum:
                outs[f"v_{p}_new"] = u["v_new"].reshape(j[p].shape)
            return
        wire = _ROUTES.get(type(node.spec), {}).get(pass_)
        if wire is None:
            raise TypeError(f"no Pallas route for spec {type(node.spec).__name__} pass {pass_!r}")
        if pass_ == "fwd":
            env[node.out_edge] = wire(wk, node, env[node.in_edge])
        elif pass_ == "dw":
            dw = reduce(wire(wk, node, env[f"d_{node.out_edge}"]))
            env[f"d_{node.param}"] = dw
            if keep_grads:
                outs[f"d_{node.param}"] = dw
        else:  # dx
            wk.add_grad(node.in_edge, wire(wk, node, env[f"d_{node.out_edge}"]))

    for seg in fusion.segments:
        if seg.region is None:
            with jax.named_scope(seg.step):
                run_step(seg.step)
            continue
        region = seg.region
        if region.batch != B:
            region = replace(region, batch=B)
        ins = {}
        for name, is_b in region.inputs:
            v = env[name] if name in env else j[name]
            ins[name] = v[None] if (is_b and not wk.batched) else v
        with jax.named_scope(region.label):
            ro = plan(region, "region")(ins)
        for name, kind in region.outputs:
            v = ro[name]
            if kind == "batched":
                env[name] = v if wk.batched else v[0]
            elif name.startswith("d_"):
                dw = reduce(v)
                env[name] = dw
                if keep_grads:
                    outs[name] = dw
            else:  # <param>_new / v_<param>_new epilogue results
                outs[name] = v
    outs[graph.logits_edge] = env[graph.logits_edge]
    # a region's loss stage leaves its rows as a column
    outs[graph.loss_rows_edge] = env[graph.loss_rows_edge].reshape(-1)
    if relu_outputs:
        outs.update((e, env[e]) for e in _relu_edges(graph, fusion))
    return outs


def _run_pallas_graph_mesh(program, inputs, interpret: bool, cache,
                           fuse=True, relu_outputs=False):
    """Data-parallel execution of a mesh-sharded train-step program.

    The batch shards over a ``(pod, data)`` jax device mesh shaped like the
    HMC mesh (the DP-axis convention of :mod:`repro.parallel.sharding`) via
    ``shard_map``; each shard runs the graph walk on its slice, and the
    gradient allreduce is a cross-shard ``psum`` — a batch *mean*, since
    the loss plan scales by 1 / B_global. Updated weights come back
    replicated, like the allgather of the command-level epilogue.

    Elastically re-sharded programs (``mesh_meta["alive"]``, set by
    :func:`repro.lower.mesh.reshard_training_step`) shard over a shrunken
    ``(1, n_alive)`` jax mesh of the survivors. Where the batch does not
    divide over them, or too few jax devices exist (the interpreter only:
    compiled kernels raise), the program runs on one device through
    :func:`_run_pallas_graph` — the same numerics, minus the parallelism.

    2D-sharded programs (``mesh_meta["shard"] == "2d"``) shard the batch
    over a ``("pipe", "data")`` jax mesh shaped like the physical grid: the
    data-parallel arithmetic the 2D command stream replays, so gradients
    match ``jax.grad`` like the 1D path. Pipeline fill/drain and tensor
    shards live in the command stream and the timing model
    (:func:`repro.runtime.mesh.time_mesh_step_2d`), not in XLA's schedule.
    Either layout needs the psum between dW and the SGD update, so regions
    keep the updates as per-node steps (``fuse_updates=False``).
    """
    import jax
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

    if jax.device_count() < n_alive and not interpret:
        raise RuntimeError(
            f"mesh program needs {n_alive} jax devices, found "
            f"{jax.device_count()}: compiled kernels never walk a mesh "
            f"program on one device (only the interpreter does)"
        )
    if jax.device_count() < n_alive or B % n_alive:
        return _run_pallas_graph(program, inputs, interpret, cache, fuse=fuse,
                                 relu_outputs=relu_outputs)
    j = _as_jax_f32(inputs)
    # inside shard_map the gradient psum must run between dW and the SGD
    # update, so regions keep the updates as per-node fallback dispatches
    fusion = _walk_plan(program, fuse, fuse_updates=False)

    # 2D programs name the axes after their meaning (pipeline rows x
    # tensor/data columns); 1D keeps the (pod, data) convention of
    # repro.parallel.sharding. Either way both axes carry batch shards.
    dp_axes = ("pipe", "data") if mesh_meta.get("shard") == "2d" else ("pod", "data")
    # a degraded mesh no longer matches the physical (rows, cols) grid:
    # lay the survivors out along one axis of a shrunken jax mesh
    jax_shape = (rows, cols) if n_alive == n else (1, n_alive)
    mesh = compat.make_mesh(jax_shape, dp_axes)
    sharded_edges = {graph.input_edge, graph.label_edge}

    def batch_spec(name):
        return P(dp_axes) if name in sharded_edges else P()

    in_specs = ({k: batch_spec(k) for k in j},)
    # the shards' logits and loss rows concatenate in batch order
    out_specs = {graph.logits_edge: P(dp_axes),
                 graph.loss_rows_edge: P(dp_axes)}
    for p in graph.param_shapes():
        out_specs[f"{p}_new"] = P()
        if keep_grads:
            out_specs[f"d_{p}"] = P()
        if graph.momentum:
            out_specs[f"v_{p}_new"] = P()
    if relu_outputs:
        # activations keep their batch shards, concatenated in batch order
        out_specs.update((e, P(dp_axes)) for e in _relu_edges(graph, fusion))

    plan = _dispatch_plan(cache, program.design.name, interpret)

    def per_shard(shard_j):
        return _graph_step_local(
            graph, shard_j, plan, B // n_alive, fusion, keep_grads=keep_grads,
            grad_reduce=lambda g: jax.lax.psum(g, dp_axes), batched=True,
            relu_outputs=relu_outputs,
        )

    return compat.shard_map(
        per_shard, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=set(dp_axes), check_vma=False,
    )(j)
