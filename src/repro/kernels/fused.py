"""Fused-region Pallas kernels: one kernel per contiguous train-step chain.

:mod:`repro.lower.fuse` groups contiguous compatible node passes of a
train-step :class:`~repro.lower.ir.NtxProgram` into
:class:`~repro.lower.fuse.RegionSpec` regions; this module compiles each
region into ONE ``pallas_call`` — the software analogue of the NTX datapath
streaming a whole loop nest through the FMAC pipeline instead of taking a
per-op offload round trip.

The kernel walks a batch-tile grid:

  * every *streamed* (batched) region input and output moves in batch
    tiles, which the Pallas pipeline double-buffers — tile k+1 streams in
    from HBM while tile k computes;
  * params and momentum state ride in as resident VMEM blocks;
  * every intermediate edge of the region lives in kernel scratch values —
    conv pre-activations, relu masks, im2col columns never touch HBM;
  * cross-batch dW reductions accumulate in VMEM scratch across grid steps
    and the SGD/momentum update runs as the last grid step's epilogue, so
    a fwd or bwd chain plus its update is one dispatch.

Convolutions are expressed per tile as statically-unrolled im2col plus an
MXU ``jnp.dot`` with an fp32 accumulator at full fp32 contraction
precision (the NTX wide-accumulation story);
the input gradient is the transposed conv — dilate dy by the stride, pad by
``k-1-p``, correlate with the rotated kernel — all inside the same tile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.lower.rules import (
    BiasSpec,
    Conv2dSpec,
    FlattenSpec,
    MatmulSpec,
    MaxPool2dSpec,
    ReluSpec,
    SoftmaxXentSpec,
)

#: Scoped VMEM a region kernel may take. The default scoped limit (16 MiB
#: on v5e) is less than a tile of a small-channel image needs once its
#: channels pad out to 128 lanes; the chip has 128 MiB.
VMEM_LIMIT_BYTES = 100 * 2**20


def _dot(a, b):
    """fp32 MXU contraction; the TPU default would round f32 to bf16."""
    return jnp.dot(
        a, b, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _batch_block(batch: int, ranks) -> int:
    """Batch-tile size: two grid steps when the batch splits evenly.

    Small tiles keep the per-tile im2col slices cheap (the measured cost
    center) while two grid steps give the pipeline something to overlap.
    ``ranks`` are those of the batched edges: in a 2-D edge the batch is
    the sublane axis, whose tile must be a multiple of 8 rows or the whole
    batch, so such regions only split batches of 16 and up.
    """
    align = 8 if min(ranks, default=3) <= 2 else 1
    half = batch // 2
    if batch >= 4 and batch % 2 == 0 and half % align == 0:
        return half
    return batch


def _pad_hw(x, ph: int, pw: int):
    if ph or pw:
        return jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    return x


def _phase_split(x, s: int, h: int, w: int):
    """(bn, H, W, c) -> (bn, h, s, w, s, c): each spatial axis as (blocks,
    phase), zero-padded on the high side to ``h*s`` x ``w*s``.

    Mosaic has no strided vector slice, and jnp lowers a slice strided in
    two axes to a gather, which Mosaic refuses too. Splitting the axes
    into stride phases turns ``x[:, a::s, b::s]`` into a unit-stride
    window of one phase.
    """
    bn, hx, wx, c = x.shape
    x = jnp.pad(x, ((0, 0), (0, h * s - hx), (0, w * s - wx), (0, 0)))
    return x.reshape(bn, h, s, w, s, c)


def _windows(xp, kh: int, kw: int, oh: int, ow: int, s: int):
    """The kh*kw im2col taps ``xp[:, dh::s, dw::s][:, :oh, :ow]``."""
    if s == 1:
        return [
            xp[:, dh : dh + oh, dw : dw + ow, :]
            for dh in range(kh)
            for dw in range(kw)
        ]
    h = max(-(-xp.shape[1] // s), (kh - 1) // s + oh)
    w = max(-(-xp.shape[2] // s), (kw - 1) // s + ow)
    r = _phase_split(xp, s, h, w)
    taps = []
    for dh in range(kh):
        for dw in range(kw):
            qh, rh = divmod(dh, s)
            qw, rw = divmod(dw, s)
            taps.append(r[:, qh : qh + oh, rh, qw : qw + ow, rw, :])
    return taps


def _conv_cols(xp, spec: Conv2dSpec):
    """Unrolled im2col on a padded tile -> (bn*oh*ow, kh*kw*cin)."""
    bn = xp.shape[0]
    oh, ow = spec.out_h, spec.out_w
    cols = jnp.concatenate(
        _windows(xp, spec.kh, spec.kw, oh, ow, spec.stride), axis=-1
    )
    return cols.reshape(bn * oh * ow, spec.kh * spec.kw * spec.cin)


def _conv_fwd_tile(x, w, spec: Conv2dSpec):
    p = spec.padding
    cols = _conv_cols(_pad_hw(x, p, p), spec)
    wf = w.reshape(spec.kh * spec.kw * spec.cin, spec.cout)
    y = _dot(cols, wf)
    return y.reshape(x.shape[0], spec.out_h, spec.out_w, spec.cout)


def _conv_dw_tile(x, dy, spec: Conv2dSpec):
    """This tile's dW contribution: cols(x)^T @ dy, batch in the contraction."""
    p = spec.padding
    cols = _conv_cols(_pad_hw(x, p, p), spec)
    dyf = dy.reshape(-1, spec.cout)
    dwf = _dot(cols.T, dyf)
    return dwf.reshape(spec.kh, spec.kw, spec.cin, spec.cout)


def _conv_dx_tile(dy, w, spec: Conv2dSpec):
    """Transposed conv per tile: dilate dy, pad by k-1-p, correlate rot180(w)."""
    bn = dy.shape[0]
    s, p = spec.stride, spec.padding
    oh, ow = spec.out_h, spec.out_w
    if s > 1:
        # dilate by the stride: dy lands on phase (0, 0) of each s x s
        # block (the inverse of _phase_split; a strided scatter would be
        # refused like the strided slice)
        z = jnp.pad(
            dy[:, :, None, :, None, :],
            ((0, 0), (0, 0), (0, s - 1), (0, 0), (0, s - 1), (0, 0)),
        ).reshape(bn, oh * s, ow * s, spec.cout)
        z = z[:, : (oh - 1) * s + 1, : (ow - 1) * s + 1, :]
    else:
        z = dy
    qh, qw = spec.kh - 1 - p, spec.kw - 1 - p
    rh = (spec.in_h + 2 * p - spec.kh) % s
    rw = (spec.in_w + 2 * p - spec.kw) % s
    z = jnp.pad(z, ((0, 0), (qh, qh + rh), (qw, qw + rw), (0, 0)))
    # rot180(w) with cin/cout swapped, tap by tap (Mosaic has no reverse):
    # (kh*kw*cout, cin) rows in the im2col's (dh, dw, cout) order
    w_hat = jnp.concatenate(
        [
            w[spec.kh - 1 - dh, spec.kw - 1 - dw].T
            for dh in range(spec.kh)
            for dw in range(spec.kw)
        ],
        axis=0,
    )
    cols = jnp.concatenate(
        _windows(z, spec.kh, spec.kw, spec.in_h, spec.in_w, 1), axis=-1
    ).reshape(bn * spec.in_h * spec.in_w, spec.kh * spec.kw * spec.cout)
    dxf = _dot(cols, w_hat)
    return dxf.reshape(bn, spec.in_h, spec.in_w, spec.cin)


def _pool_fwd_tile(x, spec: MaxPool2dSpec):
    """window == stride max pool as a reshape-max (checked by the fuser)."""
    bn, h, w, c = x.shape
    k = spec.window
    return x.reshape(bn, h // k, k, w // k, k, c).max(axis=(2, 4))


def _pool_dx_tile(x, g, spec: MaxPool2dSpec):
    """Max-pool input gradient: first-match winner mask, row-major taps.

    Ties route the gradient to the first maximal tap in window order —
    the same tie-breaking as XLA's select-and-scatter, so the fused chain
    matches ``jax.vjp`` of ``reduce_window`` bit for bit. The taps are
    walked one by one (Mosaic has no cumsum to find the first match).
    """
    bn, h, w, c = x.shape
    k = spec.window
    oh, ow = h // k, w // k
    xw = x.reshape(bn, oh, k, ow, k, c)
    y = xw.max(axis=(2, 4))
    taken = jnp.zeros(y.shape, jnp.bool_)
    rows = []
    for a in range(k):
        taps = []
        for b in range(k):
            eq = xw[:, :, a, :, b, :] == y
            taps.append(jnp.where(eq & ~taken, g, 0.0))
            taken = taken | eq
        rows.append(jnp.stack(taps, axis=3))  # (bn, oh, ow, k, c)
    return jnp.stack(rows, axis=2).reshape(bn, h, w, c)


def _flatten_tile(x):
    """(bn, h, w, c) -> (bn, h*w*c) in row-major order, pixel by pixel.

    Mosaic cannot shape-cast the tiled (w, c) minor dims into one lane
    axis, but it can concatenate the pixels' channel vectors along it.
    """
    if x.ndim != 4:
        return x.reshape(x.shape[0], -1)
    _, h, w, _ = x.shape
    return jnp.concatenate(
        [x[:, i, j, :] for i in range(h) for j in range(w)], axis=-1
    )


def _unflatten_tile(g, shape):
    """The inverse of :func:`_flatten_tile`: lane slices restacked."""
    if len(shape) != 3:
        return g.reshape((g.shape[0],) + tuple(shape))
    h, w, c = shape
    return jnp.stack(
        [
            jnp.stack(
                [g[:, (i * w + j) * c : (i * w + j + 1) * c] for j in range(w)],
                axis=1,
            )
            for i in range(h)
        ],
        axis=1,
    )


def _stage_flow(region, env):
    """Run the region's dataflow stages on one batch tile.

    ``env`` maps edge names to tile values (leading ``bn`` axis on batched
    edges); gains every intermediate and stage output. Returns ``(env,
    partials)`` where ``partials`` holds this tile's contribution to each
    cross-batch ``d_<param>`` reduction. ``upd`` stages run later, in
    :func:`_stage_updates`, once the reduction is complete.
    """
    partials = {}
    for st in region.stages:
        s = st.spec
        if st.pass_ == "fwd":
            x = env[st.in_edge]
            if isinstance(s, Conv2dSpec):
                y = _conv_fwd_tile(x, env[st.param], s)
            elif isinstance(s, MatmulSpec):
                y = _dot(x, env[st.param])
            elif isinstance(s, BiasSpec):
                y = x + env[st.param]
            elif isinstance(s, ReluSpec):
                y = jnp.maximum(x, 0.0)
            elif isinstance(s, MaxPool2dSpec):
                y = _pool_fwd_tile(x, s)
            elif isinstance(s, FlattenSpec):
                y = _flatten_tile(x)
            else:
                raise TypeError(f"no fused fwd rule for {type(s).__name__}")
            env[st.out_edge] = y
        elif st.pass_ == "dw":
            g = env[f"d_{st.out_edge}"]
            if isinstance(s, Conv2dSpec):
                d = _conv_dw_tile(env[st.in_edge], g, s)
            elif isinstance(s, MatmulSpec):
                d = _dot(env[st.in_edge].T, g)
            elif isinstance(s, BiasSpec):
                d = g.reshape(-1, s.c).sum(axis=0)
            else:
                raise TypeError(f"no fused dW rule for {type(s).__name__}")
            partials[f"d_{st.param}"] = d
        elif st.pass_ == "dx":
            if isinstance(s, SoftmaxXentSpec):
                # softmax-CE loss gradient (softmax(z) - onehot) / B: rows
                # are independent, so the batch-tile split is exact; the
                # 1/B scale uses the spec's global batch, and the onehot
                # labels arrive via the stage's param slot
                z = env[st.in_edge]
                env[f"d_{st.in_edge}"] = (
                    jax.nn.softmax(z, axis=-1) - env[st.param]
                ) / s.batch
                continue
            g = env[f"d_{st.out_edge}"]
            if isinstance(s, Conv2dSpec):
                dx = _conv_dx_tile(g, env[st.param], s)
            elif isinstance(s, MatmulSpec):
                dx = _dot(g, env[st.param].T)
            elif isinstance(s, ReluSpec):
                # mask from the relu *output*: y > 0 iff x > 0, so the
                # pre-activation never has to escape its forward region
                dx = jnp.where(env[st.out_edge] > 0.0, g, 0.0)
            elif isinstance(s, MaxPool2dSpec):
                dx = _pool_dx_tile(env[st.in_edge], g, s)
            elif isinstance(s, FlattenSpec):
                dx = _unflatten_tile(g, s.in_shape)
            elif isinstance(s, BiasSpec):
                dx = g
            else:
                raise TypeError(f"no fused dX rule for {type(s).__name__}")
            env[f"d_{st.in_edge}"] = dx
        elif st.pass_ != "upd":
            raise TypeError(f"unknown pass {st.pass_!r} in fused region")
    return env, partials


def _stage_updates(region, totals, env):
    """SGD/momentum epilogue on the fully reduced dW totals."""
    outs = {}
    for st in region.stages:
        if st.pass_ != "upd":
            continue
        p = st.param
        # the gradient total normally accumulates in-region; when a spill
        # or chain barrier split the dw stage into an earlier region, the
        # already-reduced total arrives as a resident input instead
        dw = totals.get(f"d_{p}")
        if dw is None:
            dw = env[f"d_{p}"]
        if region.momentum:
            v_new = region.momentum * env[f"v_{p}"] + dw
            outs[f"v_{p}_new"] = v_new
        else:
            v_new = dw
        outs[f"{p}_new"] = env[p] - region.lr * v_new
    return outs


def build_region_callable(region, *, interpret: bool):
    """Compile one RegionSpec into a dict -> dict jax callable.

    The callable takes the region's input edges (batched activations /
    gradients plus resident params) and returns its escaping edges; it is
    what the :class:`~repro.lower.executors.PlanCache` jits under the
    region key, so the whole chain is one cached dispatch.
    """
    streamed = [n for n, b in region.inputs if b]
    resident = [n for n, b in region.inputs if not b]
    batched_outs = [n for n, k in region.outputs if k == "batched"]
    reduced_outs = [n for n, k in region.outputs if k == "reduced"]
    out_names = batched_outs + reduced_outs
    acc_names = [f"d_{st.param}" for st in region.stages if st.pass_ == "dw"]
    has_epilogue = bool(acc_names) or any(
        st.pass_ == "upd" for st in region.stages
    )
    n_s, n_r, n_o = len(streamed), len(resident), len(out_names)

    def fn(j):
        def probe(vals):
            env, partials = _stage_flow(region, dict(vals))
            return env, partials

        env_sh, part_sh = jax.eval_shape(probe, j)

        def out_struct(name):
            if name in part_sh:
                return part_sh[name]
            if name.endswith("_new"):
                base = name[:-4]
                return jax.ShapeDtypeStruct(j[base].shape, jnp.float32)
            return env_sh[name]

        B = region.batch
        bn = _batch_block(
            B,
            [len(j[n].shape) for n in streamed]
            + [len(out_struct(n).shape) for n in batched_outs],
        )
        grid = B // bn

        in_specs = []
        for name in streamed:
            shp = tuple(j[name].shape)
            in_specs.append(pl.BlockSpec((bn,) + shp[1:], _lead_map(len(shp))))
        for name in resident:
            shp = tuple(j[name].shape)
            in_specs.append(pl.BlockSpec(shp, _const_map(len(shp))))
        out_specs, out_shape = [], []
        for name in batched_outs:
            shp = tuple(out_struct(name).shape)
            out_specs.append(
                pl.BlockSpec((bn,) + shp[1:], _lead_map(len(shp)))
            )
            out_shape.append(jax.ShapeDtypeStruct(shp, jnp.float32))
        for name in reduced_outs:
            shp = tuple(out_struct(name).shape)
            out_specs.append(pl.BlockSpec(shp, _const_map(len(shp))))
            out_shape.append(jax.ShapeDtypeStruct(shp, jnp.float32))

        scratch = [
            pltpu.VMEM(tuple(part_sh[name].shape), jnp.float32)
            for name in acc_names
        ]

        def kernel(*refs):
            irefs = refs[: n_s + n_r]
            orefs = refs[n_s + n_r : n_s + n_r + n_o]
            accs = refs[n_s + n_r + n_o :]
            gi = pl.program_id(0)

            names = streamed + resident
            env = {name: irefs[i][...] for i, name in enumerate(names)}
            env, partials = _stage_flow(region, env)

            if acc_names:

                @pl.when(gi == 0)
                def _():
                    for i in range(len(acc_names)):
                        accs[i][...] = jnp.zeros(accs[i].shape, jnp.float32)

                for i, name in enumerate(acc_names):
                    accs[i][...] += partials[name]

            for i, name in enumerate(batched_outs):
                orefs[i][...] = env[name]

            if has_epilogue:

                @pl.when(gi == grid - 1)
                def _():
                    totals = {
                        name: accs[i][...] for i, name in enumerate(acc_names)
                    }
                    upd = _stage_updates(region, totals, env)
                    for i, name in enumerate(reduced_outs):
                        oref = orefs[len(batched_outs) + i]
                        oref[...] = upd[name] if name in upd else totals[name]

        res = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            # the dW accumulators carry across batch tiles, so the grid
            # runs in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES,
            ),
            interpret=interpret,
        )(*[j[n] for n in streamed], *[j[n] for n in resident])
        if not isinstance(res, (list, tuple)):
            res = (res,)
        return dict(zip(out_names, res))

    return fn


def _const_map(rank: int):
    return lambda i: (0,) * rank


def _lead_map(rank: int):
    return lambda i: (i,) + (0,) * (rank - 1)
