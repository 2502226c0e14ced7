"""NTX conv2d — the paper's primary workload kernel, on Pallas/TPU.

A direct convolution written exactly as the NtxCommand of §2.4 executes it:
the grid iterates output tiles (the driver's offload loop), the kernel body
runs the (kh, kw) reduction loops with the channel contraction on the MXU,
and the fp32 accumulator lives in VMEM until the single deferred store (C1).
Output tiles overlap on their input halo, so the input plane is kept whole
per batch element and the kernel slices its slab with a dynamic row offset
(the AGU address calculation, eq. 1); `core/tiling.plan_stencil_tiles`
guarantees the slab fits VMEM at the sizes the framework uses.

Layout: NHWC x HWIO -> NHWC, stride >= 1, VALID padding (callers pad).
Strided output is computed by strided VMEM slicing — the forward counterpart
of the paper's §3.2 backward decomposition (constant MACs per output pixel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _conv_kernel(x_ref, w_ref, o_ref, acc_ref, *, kh, kw, stride, th, ow, slab_h):
    """One (1, th, ow, Cout) output tile; x_ref holds the full (padded) plane."""
    t = pl.program_id(1)
    row0 = t * th * stride
    cin = x_ref.shape[-1]
    cout = o_ref.shape[-1]
    slab = x_ref[0, pl.dslice(row0, slab_h)]  # (slab_h, W, Cin)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    for u in range(kh):
        for v in range(kw):
            xs = jax.lax.slice(
                slab,
                (u, v, 0),
                (u + (th - 1) * stride + 1, v + (ow - 1) * stride + 1, cin),
                (stride, stride, 1),
            )  # (th, ow, cin)
            acc_ref[...] += jnp.dot(
                xs.reshape(th * ow, cin), w_ref[u, v],
                preferred_element_type=jnp.float32,
            ).reshape(th, ow, cout)
    o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def conv2d_ntx(
    x: jnp.ndarray,  # (N, H, W, Cin) — pre-padded
    w: jnp.ndarray,  # (kh, kw, Cin, Cout)
    *,
    stride: int = 1,
    tile_h: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    n, h, wid, cin = x.shape
    kh, kw, _, cout = w.shape
    oh = (h - kh) // stride + 1
    ow = (wid - kw) // stride + 1
    th = min(tile_h, oh)
    n_tiles = -(-oh // th)
    pad_rows = (n_tiles * th - oh) * stride
    if pad_rows:
        x = jnp.pad(x, ((0, 0), (0, pad_rows), (0, 0), (0, 0)))
    slab_h = (th - 1) * stride + kh

    kernel = functools.partial(
        _conv_kernel, kh=kh, kw=kw, stride=stride, th=th, ow=ow, slab_h=slab_h
    )
    out = pl.pallas_call(
        kernel,
        grid=(n, n_tiles),
        in_specs=[
            pl.BlockSpec((1, x.shape[1], wid, cin), lambda b, t: (b, 0, 0, 0)),
            pl.BlockSpec((kh, kw, cin, cout), lambda b, t: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, th, ow, cout), lambda b, t: (b, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n_tiles * th, ow, cout), x.dtype),
        scratch_shapes=[pltpu.VMEM((th, ow, cout), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w)
    return out[:, :oh]
