"""NTX FMAC matmul — the paper's datapath (C1+C3) as a Pallas TPU kernel.

The kernel realizes, on MXU/VMEM, exactly what the NTX cluster does with its
FMAC + TCDM + DMA:

  * 3-deep ``grid`` = the hardware loops that the driver offloads once per
    tile (C2): one ``pallas_call`` covers the whole output, like one NTX
    command covers many output pixels;
  * BlockSpec index maps = the AGU address equations (eq. 1);
  * the Pallas pipeline double-buffers HBM->VMEM tile copies behind compute =
    the cluster DMA (C3);
  * the fp32 VMEM accumulator with deferred rounding = the PCS accumulator
    (C1): for bf16 inputs every MXU product is *exact* in fp32, and for fp32
    inputs an optional compensated (2Sum) accumulator halves the exponent of
    the K-direction error growth, reproducing Table 1's "better than an fp32
    FPU" property.

Block shapes come from :mod:`repro.core.tiling` so the working set provably
fits VMEM and matmul dims are 128-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import two_sum
from repro.core.tiling import plan_matmul_tiles


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, comp_ref, *, k_tiles: int, compensated: bool):
    """One (bm, bn) output tile; K accumulated across the innermost grid dim."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if compensated:
            comp_ref[...] = jnp.zeros_like(comp_ref)

    prod = jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32)
    if compensated:
        # 2Sum across K-tiles: accumulator error is O(eps), not O(k_tiles*eps).
        s, e = two_sum(acc_ref[...], prod)
        acc_ref[...] = s
        comp_ref[...] += e
    else:
        acc_ref[...] += prod

    @pl.when(pl.program_id(2) == k_tiles - 1)
    def _store():
        # Deferred rounding: the accumulator leaves VMEM exactly once.
        out = acc_ref[...]
        if compensated:
            out = out + comp_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


def ntx_matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    out_dtype=jnp.float32,
    compensated: bool = False,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """C[M,N] = A[M,K] @ B[K,N] with NTX wide accumulation.

    Shapes must tile evenly by the chosen blocks (the ops wrapper pads).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    in_bytes = max(a.dtype.itemsize, b.dtype.itemsize)
    plan = plan_matmul_tiles(m, n, k, in_dtype_bytes=in_bytes)
    bm = block_m or min(plan.bm, m)
    bn = block_n or min(plan.bn, n)
    bk = block_k or min(plan.bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{n},{k}) must tile by ({bm},{bn},{bk}); use ops.matmul for padding"
    )
    k_tiles = k // bk

    grid = (m // bm, n // bn, k_tiles)
    kernel = functools.partial(_matmul_kernel, k_tiles=k_tiles, compensated=compensated)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
