"""Explicitly double-buffered streaming kernels — the runtime model on TPU.

Where :mod:`repro.kernels.ntx_matmul` lets the Pallas pipeline helper do the
HBM->VMEM staging implicitly, this module writes the cluster-DMA flow out by
hand, exactly as :mod:`repro.runtime` models it: inputs stay in HBM/ANY
memory, the kernel owns two VMEM tile buffers per operand, and a manual
``make_async_copy`` prefetches tile k+1 while the MXU contracts tile k. One
grid step = one NTX command queue entry; the k-loop inside the kernel = the
double-buffered DMA engine of :mod:`repro.runtime.dma`.

The fp32 VMEM accumulator with a single deferred store keeps the NTX wide-
accumulation (C1) story. Numerics are cross-checked against
:func:`repro.kernels.ref.matmul_ref`; the tile schedule's modeled cycles are
cross-checked against the runtime in ``tests/test_runtime_queue.py`` via
:func:`streaming_tiles`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N_BUFFERS = 2  # double buffering, as in runtime.dma.DmaConfig


def _stream_mm_kernel(a_hbm, b_hbm, o_ref, *, bm, bn, bk, k_tiles, a_dtype, b_dtype):
    # f32 operands contract at full fp32 on the MXU; the default would
    # round them to bf16 passes on TPU
    precision = jax.lax.Precision.HIGHEST if a_dtype == jnp.float32 else None

    i = pl.program_id(0)
    j = pl.program_id(1)

    def body(a_buf, b_buf, acc_ref, sem):
        def copies(slot, kk):
            a_cp = pltpu.make_async_copy(
                a_hbm.at[pl.ds(i * bm, bm), pl.ds(kk * bk, bk)],
                a_buf.at[slot], sem.at[slot, 0],
            )
            b_cp = pltpu.make_async_copy(
                b_hbm.at[pl.ds(kk * bk, bk), pl.ds(j * bn, bn)],
                b_buf.at[slot], sem.at[slot, 1],
            )
            return a_cp, b_cp

        def start(slot, kk):
            for cp in copies(slot, kk):
                cp.start()

        def wait(slot, kk):
            for cp in copies(slot, kk):
                cp.wait()

        start(0, 0)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def k_step(kk, carry):
            cur = jax.lax.rem(kk, N_BUFFERS)
            nxt = jax.lax.rem(kk + 1, N_BUFFERS)

            @pl.when(kk + 1 < k_tiles)
            def _prefetch():  # next tile streams in while this one computes
                start(nxt, kk + 1)

            wait(cur, kk)
            acc_ref[...] += jnp.dot(
                a_buf[cur], b_buf[cur], preferred_element_type=jnp.float32,
                precision=precision,
            )
            return carry

        jax.lax.fori_loop(0, k_tiles, k_step, 0)
        # deferred rounding: the accumulator leaves VMEM exactly once
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    pl.run_scoped(
        body,
        a_buf=pltpu.VMEM((N_BUFFERS, bm, bk), a_dtype),
        b_buf=pltpu.VMEM((N_BUFFERS, bk, bn), b_dtype),
        acc_ref=pltpu.VMEM((bm, bn), jnp.float32),
        sem=pltpu.SemaphoreType.DMA((N_BUFFERS, 2)),
    )


LANE = 128  # TPU vreg lanes: the minor dim of every VMEM tile


def _sublane(itemsize: int) -> int:
    """Rows of one (sublane, 128) VMEM tile: 8 for 32-bit, 16 for 16-bit."""
    return 8 * max(1, 4 // itemsize)


def _block(dim: int, align: int) -> int:
    """Tile edge: 128, or the next power of two at or above ``dim`` but
    never below ``align`` — Mosaic slices HBM only in whole tiles, so a
    small dim pads up to one tile and the result is sliced back."""
    return LANE if dim >= LANE else max(align, 1 << (dim - 1).bit_length())


def _blocks(m: int, n: int, k: int, sub: int, block_m, block_n, block_k):
    """(bm, bn, bk): rows on the sublane tiling, N and K on whole lanes.

    K is A's minor (lane) dim and N is B's, so both need ``LANE``-wide
    tiles; M only needs whole sublane groups.
    """
    return (
        block_m or _block(m, sub),
        block_n or _block(n, LANE),
        block_k or _block(k, LANE),
    )


def _pad_to(x: jnp.ndarray, mult: tuple[int, int]) -> jnp.ndarray:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mult)]
    return jnp.pad(x, pads) if any(p[1] for p in pads) else x


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "block_m", "block_n", "block_k", "interpret")
)
def streaming_matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    out_dtype=jnp.float32,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """C[M,N] = A[M,K] @ B[K,N] with hand-rolled double-buffered streaming."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = _blocks(m, n, k, _sublane(a.dtype.itemsize), block_m, block_n, block_k)
    ap = _pad_to(a, (bm, bk))
    bp = _pad_to(b, (bk, bn))
    mp, kp = ap.shape
    _, np_ = bp.shape
    k_tiles = kp // bk

    kernel = functools.partial(
        _stream_mm_kernel, bm=bm, bn=bn, bk=bk, k_tiles=k_tiles,
        a_dtype=ap.dtype, b_dtype=bp.dtype,
    )
    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n]


def streaming_conv2d(
    x: jnp.ndarray,  # (N, H, W, Cin)
    w: jnp.ndarray,  # (KH, KW, Cin, Cout)
    *,
    stride: int = 1,
    padding: int = 0,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """NHWC x HWIO conv as an im2col streaming matmul (the paper's conv map).

    The (kh, kw, cin) reduction dims flatten into the streamed K axis —
    the same loop order :func:`repro.lower.rules.conv2d_fwd_template` gives
    the AGUs.
    """
    n, h, wid, cin = x.shape
    kh, kw, _, cout = w.shape
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
        h, wid = h + 2 * padding, wid + 2 * padding
    oh = (h - kh) // stride + 1
    ow = (wid - kw) // stride + 1
    cols = jnp.concatenate(
        [
            x[:, dh : dh + oh * stride : stride, dw : dw + ow * stride : stride, :]
            for dh in range(kh)
            for dw in range(kw)
        ],
        axis=-1,
    )  # (N, OH, OW, KH*KW*Cin) in (kh, kw, cin) order
    lhs = cols.reshape(n * oh * ow, kh * kw * cin)
    rhs = w.reshape(kh * kw * cin, cout)
    y = streaming_matmul(lhs, rhs, out_dtype=out_dtype, interpret=interpret)
    return y.reshape(n, oh, ow, cout)


def streaming_tiles(
    m: int, n: int, k: int,
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    itemsize: int = 4,
) -> list[tuple[float, float]]:
    """The kernel's exact tile stream as (dma_bytes, macs) pairs.

    One entry per (i, j, kk) inner step, in issue order — what the manual
    DMA engine above actually transfers and contracts. Feeding this to
    :class:`repro.runtime.dma.DmaEngine` (or wrapping each entry in an
    ``NtxCommand``) yields the runtime's cycle estimate for this kernel.
    """
    bm, bn, bk = _blocks(m, n, k, _sublane(itemsize), block_m, block_n, block_k)
    mp, np_, kp = -(-m // bm) * bm, -(-n // bn) * bn, -(-k // bk) * bk
    tiles = []
    for _i in range(mp // bm):
        for _j in range(np_ // bn):
            for _kk in range(kp // bk):
                tiles.append(((bm * bk + bk * bn) * itemsize, float(bm * bn * bk)))
    return tiles
