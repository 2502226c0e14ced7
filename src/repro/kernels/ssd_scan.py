"""Mamba-2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

The SSD layer is itself a five-deep loop nest (batch, head, chunk, position,
state) — precisely the shape of an NtxCommand (C2) — and its chunked "dual
form" is the NTX streaming pattern: a quadratic-in-chunk dense block handled
by the MXU plus a small recurrent state carried across chunks in fp32 VMEM
scratch (C1's wide accumulator again: the state never leaves VMEM and is
rounded only when written).

Recurrence (per batch, head; x_t in R^P, b_t, c_t in R^N, a_t = exp(la_t)):

    h_t = a_t * h_{t-1} + x_t b_t^T          (P, N)
    y_t = h_t c_t                             (P,)

Chunked dual form over chunks of length Q with inclusive cumsum cum_i of la:

    y_intra[i] = sum_{j<=i} exp(cum_i - cum_j) (c_i . b_j) x_j     — MXU block
    y_inter[i] = exp(cum_i) * (h_prev c_i)                          — state read
    h_next     = exp(cum_{Q-1}) h_prev
                 + sum_j exp(cum_{Q-1} - cum_j) x_j b_j^T           — state write

The chunk grid dimension is sequential ("arbitrary"), the state persists in
scratch across grid steps — Pallas's analogue of the NTX accumulator
surviving loop iterations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, la_ref, b_ref, c_ref, y_ref, h_scr, *, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    la = la_ref[0, 0].astype(jnp.float32)  # (Q,) log-decay, <= 0
    b = b_ref[0, 0].astype(jnp.float32)  # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)  # (Q, N)
    q = x.shape[0]

    cum = jnp.cumsum(la)  # inclusive; (Q,)
    total = cum[-1]

    # Intra-chunk: causal decay-weighted score block on the MXU.
    scores = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q): scores[i, j] = c_i . b_j
    li = cum[:, None] - cum[None, :]  # log decay i<-j
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    )
    decay = jnp.where(causal, jnp.exp(li), 0.0)
    y = jnp.dot(scores * decay, x, preferred_element_type=jnp.float32)  # (Q, P)

    # Inter-chunk: contribution of the carried state.
    h = h_scr[...]  # (P, N) fp32
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        c, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, P)

    # State update (wide accumulator never leaves VMEM between chunks).
    w = jnp.exp(total - cum)[:, None] * b  # (Q, N)
    h_scr[...] = jnp.exp(total) * h + jax.lax.dot_general(
        x, w, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)

    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_scan(
    x: jnp.ndarray,  # (B, H, S, P)
    la: jnp.ndarray,  # (B, H, S) log decay (<= 0)
    b: jnp.ndarray,  # (B, G, S, N)
    c: jnp.ndarray,  # (B, G, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Chunked SSD scan; returns y with shape (B, H, S, P)."""
    bb, h, s, p = x.shape
    _, g, _, n = b.shape
    assert h % g == 0, (h, g)
    grp = h // g
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    grid = (bb, h, n_chunks)

    kernel = functools.partial(_ssd_kernel, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bi, hi, ci: (bi, hi, ci)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci, g=grp: (bi, hi // g, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci, g=grp: (bi, hi // g, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bb, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, la, b, c)
