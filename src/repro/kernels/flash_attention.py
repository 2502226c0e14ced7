"""Blockwise-softmax attention (flash attention) as a Pallas TPU kernel.

Attention is the compute hot-spot of every assigned LM architecture, and it is
built here in full NTX style (C1+C2+C3): the score/renormalization statistics
and the output accumulator live in fp32 VMEM scratch for the whole KV sweep and
are rounded exactly once at the store — the PCS-accumulator discipline applied
to the online-softmax recurrence. The (q_block, kv_block) grid is the offloaded
loop nest; BlockSpec index maps implement GQA by pointing a group of q-heads at
their shared kv-head without replicating KV in HBM.

Supports causal masking and sliding-window (Mistral/local-attention) masking.
Fully-masked kv blocks are skipped with ``pl.when`` (compute saved; the DMA
still streams them — see EXPERIMENTS.md §Perf for the measured effect).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
_LANES = 128


def _attn_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    kv_blocks: int,
    block_q: int,
    block_kv: int,
    causal: bool,
    window: int | None,
    sm_scale: float,
    kv_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    kv_start = ki * block_kv

    # Static-shape block skip decision must be dynamic (traced), so use when().
    def visible():
        v = jnp.bool_(True)
        if causal:
            v = jnp.logical_and(v, kv_start <= q_start + block_q - 1)
        if window is not None:
            v = jnp.logical_and(v, kv_start + block_kv - 1 >= q_start - window)
        return v

    @pl.when(visible())
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bkv, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bkv)
        s *= sm_scale

        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kv_ids = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = kv_ids < kv_len  # tail padding
        if causal:
            mask = jnp.logical_and(mask, kv_ids <= q_ids)
        if window is not None:
            mask = jnp.logical_and(mask, kv_ids > q_ids - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # (bq, LANES) broadcast stats
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)  # rescale of old stats
        p = jnp.exp(s - m_new[:, :1])  # (bq, bkv)
        # Rows with no visible key yet: m_new == NEG_INF -> p must be 0.
        p = jnp.where(jnp.broadcast_to(m_new[:, :1] <= NEG_INF / 2, p.shape), 0.0, p)
        alpha = jnp.where(m_new <= NEG_INF / 2, 0.0, alpha)

        l_scr[...] = l_prev * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape
        )
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )

    @pl.when(ki == kv_blocks - 1)
    def _store():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zero output
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,  # (B, Hq, Sq, D)
    k: jnp.ndarray,  # (B, Hkv, Skv, D)
    v: jnp.ndarray,  # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    window: int | None = None,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-head attention with GQA via index maps (no KV replication)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)

    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0, (sq, block_q)
    pad_kv = (-skv) % block_kv
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    kv_blocks = k.shape[2] // block_kv
    grid = (b, hq, sq // block_q, kv_blocks)

    kernel = functools.partial(
        _attn_kernel,
        kv_blocks=kv_blocks,
        block_q=block_q,
        block_kv=block_kv,
        causal=causal,
        window=window,
        sm_scale=sm_scale,
        kv_len=skv,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda bi, h, qi, ki, g=group: (bi, h // g, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda bi, h, qi, ki, g=group: (bi, h // g, ki, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
