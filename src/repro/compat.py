"""The mesh and ``shard_map`` spellings this codebase uses, in one place.

``make_mesh`` builds meshes whose axes are all Auto (the sharding rules in
:mod:`repro.parallel.sharding` rely on GSPMD propagation), and
``shard_map`` turns a set of manual axes into ``jax.shard_map``'s
``axis_names`` with value-variance checking off by default.
"""

from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis Auto."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None, check_vma=False):
    """``jax.shard_map``; ``axis_names`` (default: all axes) are manual."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)
