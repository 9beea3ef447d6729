"""Mesh and shard_map construction in one place.

Everything that builds meshes or shard_maps goes through this module,
so the call sites share one set of defaults (Auto mesh axis types).
"""

from __future__ import annotations

from typing import Sequence

import jax


def shard_map(f, mesh, in_specs, out_specs, *, check_vma=True):
    """``jax.shard_map`` over ``mesh``. ``check_vma=False`` for bodies
    that call Pallas kernels, whose outputs carry no varying-axes type."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
    )
