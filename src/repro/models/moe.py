"""Mixture-of-Experts FFN with expert parallelism (EP) on the "model" axis.

Two interchangeable implementations (property-tested against each other):

* ``moe_dense``  — exact: every expert computed for every token, combined with
  router weights. O(E) compute; used at smoke-test scale and as the oracle.
* ``moe_ep``     — production: experts sharded over the mesh "model" axis via a
  partial-manual ``shard_map``. Because activations are replicated across the
  TP axis between blocks (Megatron-style), each model-rank *already holds every
  token* — dispatch needs **zero communication**: a rank gathers the
  (token, k) pairs routed to its local experts into capacity-bounded buffers,
  runs its expert FFNs, scatters weighted outputs back, and a single
  ``psum`` over "model" combines ranks (the same collective a dense TP MLP
  needs). This is the NTX lesson (C3) applied to MoE: move compute to where
  the data already is instead of re-tiling/re-sharding it.

Capacity: each rank processes at most ``C = ceil(T*K/n_ranks * cap_factor)``
pairs, padded/dropped GShard-style; dropped tokens keep only their other-k
contributions. Router: softmax -> top-k, renormalized; load-balance and
router-z auxiliary losses are returned for the trainer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.models.blocks import _dot


def init_moe(rng, cfg, dtype=jnp.bfloat16):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(rng, 5)
    std = d**-0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * std).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * std).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) * std).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) * f**-0.5).astype(dtype),
    }
    if cfg.shared_expert_d_ff:
        from repro.models.blocks import init_mlp

        p["shared"] = init_mlp(ks[4], d, cfg.shared_expert_d_ff, cfg.mlp_act, dtype)
    return p


def route(x2d: jnp.ndarray, router_w: jnp.ndarray, top_k: int):
    """Softmax-then-top-k routing. Returns (weights (T,K) fp32, ids (T,K), aux).

    Logits accumulate in fp32 but x2d is consumed in its own dtype — creating
    an fp32 copy of the activations here makes GSPMD gather fp32 activations
    for the EP body too (2x the wire bytes; §Perf B-H3).
    """
    logits = jnp.dot(
        x2d, router_w.astype(x2d.dtype), preferred_element_type=jnp.float32
    )  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, top_k)  # (T, K)
    w = w / jnp.clip(w.sum(-1, keepdims=True), 1e-9)
    # GShard load-balance loss + router z-loss.
    e = router_w.shape[1]
    me = probs.mean(0)  # (E,) mean prob
    one_hot = jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32)  # top-1 assignment share
    ce = one_hot.mean(0)
    aux = {
        "load_balance": e * jnp.sum(me * ce),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return w, ids, aux


def _expert_ffn(xe: jnp.ndarray, wg, wu, wd, act: str) -> jnp.ndarray:
    """xe: (E_local, C, D); expert weights (E_local, D, F) / (E_local, F, D)."""
    h = jnp.einsum("ecd,edf->ecf", xe, wg, preferred_element_type=jnp.float32)
    if act in ("swiglu", "geglu"):
        gate = jax.nn.silu(h) if act == "swiglu" else jax.nn.gelu(h)
        up = jnp.einsum("ecd,edf->ecf", xe, wu, preferred_element_type=jnp.float32)
        h = gate * up
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h.astype(xe.dtype), wd, preferred_element_type=jnp.float32)


def moe_dense(x: jnp.ndarray, params, cfg):
    """Exact O(E) reference: all experts on all tokens (smoke scale / oracle)."""
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    w, ids, aux = route(x2, params["router"], cfg.top_k)
    e = cfg.n_experts
    # combine(T, E) from top-k
    comb = jnp.zeros((b * s, e), jnp.float32)
    comb = jax.vmap(lambda c, i, v: c.at[i].add(v))(comb, ids, w)
    y_all = _expert_ffn(
        jnp.broadcast_to(x2, (e,) + x2.shape).astype(x.dtype),
        params["w_gate"],
        params["w_up"],
        params["w_down"],
        cfg.mlp_act,
    )  # (E, T, D)
    y = jnp.einsum("etd,te->td", y_all, comb).astype(x.dtype)
    if "shared" in params:
        from repro.models.blocks import mlp

        y = y + mlp(x2, params["shared"], cfg.mlp_act)
    return y.reshape(b, s, d), aux


def _moe_rank_body(x2, comb, wg, wu, wd, *, e_local, cap, act, gather_axis):
    """Per-(dp, model)-rank EP body (runs inside a manual shard_map region).

    ``x2`` is dp-local; ``comb`` is the (T, E_local) slice of the combine
    matrix — sharded over "model", so its cotangent stays rank-local (passing
    the replicated (T,K) routing tensors instead makes their backward a psum
    storm over "model": the dominant collective of the first MoE baseline,
    see EXPERIMENTS.md §Perf B-H2). Expert weights are model-rank-local with
    the FFN dim FSDP-sharded over ``gather_axis`` — gathered transiently, so
    the resident footprint of a 400B expert bank is params/(model*data)/chip.
    """
    if gather_axis:
        wg = jax.lax.all_gather(wg, gather_axis, axis=2, tiled=True)
        wu = jax.lax.all_gather(wu, gather_axis, axis=2, tiled=True)
        wd = jax.lax.all_gather(wd, gather_axis, axis=1, tiled=True)
    t, d = x2.shape

    y = jnp.zeros((t, d), jnp.float32)
    for le in range(e_local):
        # (T,) routing weight of this expert for each token (0 if not routed).
        w_e = comb[:, le]
        m = w_e > 0.0
        # Capacity slots (first-come order, GShard-style dropping).
        slot = jnp.cumsum(m.astype(jnp.int32)) - 1
        slot = jnp.where(m & (slot < cap), slot, cap)  # overflow -> slot `cap`
        buf = jnp.zeros((cap + 1, d), x2.dtype).at[slot].add(
            jnp.where(m[:, None], x2, 0).astype(x2.dtype)
        )
        ye = _expert_ffn(buf[None, :cap], wg[le : le + 1], wu[le : le + 1], wd[le : le + 1], act)[0]
        ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], axis=0)  # overflow row
        y = y + ye[slot].astype(jnp.float32) * w_e[:, None]
    # Combine happens *outside* the manual region (stacked over "model" and
    # summed in the auto region): an in-body psum of bf16 partials gets
    # re-upcast to f32 by the psum_invariant lowering (§Perf B-H1/B-H4), while
    # the auto-region reduction keeps bf16 and lets GSPMD pick AR vs RS+AG.
    return y.astype(x2.dtype)[None]


def moe_ep(x: jnp.ndarray, params, cfg, mesh, dp_axes: tuple[str, ...] = ()):
    """Expert-parallel MoE over the mesh "model" axis (production path).

    ``dp_axes``: mesh axes the token/batch dim is sharded over — they join the
    manual set so capacity bookkeeping (cumsum, slots) stays shard-local and
    never couples dp shards.
    """
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    w, ids, aux = route(x2, params["router"], cfg.top_k)
    # Dense (T, E) combine matrix, sharded over experts ("model") on entry.
    t = b * s
    comb = jnp.zeros((t, cfg.n_experts), jnp.float32)
    comb = comb.at[jnp.arange(t)[:, None], ids].add(w)

    n_ranks = mesh.shape["model"]
    e_local = cfg.n_experts // n_ranks
    assert cfg.n_experts % n_ranks == 0, (cfg.n_experts, n_ranks)
    dp_degree = 1
    for a in dp_axes:
        dp_degree *= mesh.shape[a]
    # Per-rank capacity: expected T_local*K/n_ranks pairs, padded by the factor.
    t_local = b * s // dp_degree  # tokens per dp shard (replicated across model)
    cap_rank = int((t_local * cfg.top_k / n_ranks) * cfg.capacity_factor + 0.999)
    cap = max(8, -(-cap_rank // e_local))  # per local expert

    gather_axis = "data" if ("data" in dp_axes and cfg.moe_d_ff % mesh.shape["data"] == 0) else None
    body = functools.partial(
        _moe_rank_body, e_local=e_local, cap=cap, act=cfg.mlp_act, gather_axis=gather_axis
    )
    tok = P(dp_axes) if dp_axes else P()
    comb_spec = P(dp_axes if dp_axes else None, "model")
    wgu_spec = P("model", None, gather_axis)
    wd_spec = P("model", gather_axis, None)
    # When nested inside a manual region (the systolic train step), shard_map
    # must be given the surrounding *abstract* mesh, not the concrete one.
    ctx_mesh = jax.sharding.get_abstract_mesh()
    sm_mesh = ctx_mesh if (ctx_mesh is not None and ctx_mesh.shape) else mesh
    out_spec = P(("model",) ,*( [dp_axes] if dp_axes else [None]), None)
    y = compat.shard_map(
        body,
        mesh=sm_mesh,
        in_specs=(tok, comb_spec, wgu_spec, wgu_spec, wd_spec),
        out_specs=out_spec,
        axis_names=set(dp_axes) | {"model"},
        check_vma=True,
    )(x2, comb, params["w_gate"], params["w_up"], params["w_down"])
    y = y.sum(axis=0).astype(x.dtype)  # combine ranks in the auto region
    if "shared" in params:
        from repro.models.blocks import mlp

        y = y + mlp(x2, params["shared"], cfg.mlp_act)
    return y.reshape(b, s, d), aux
