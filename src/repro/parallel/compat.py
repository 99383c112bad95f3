"""Mesh and shard_map construction in one place.

The repo targets the installed jax (0.9): ``jax.shard_map`` with
``check_vma`` and ``axis_names``, meshes with explicit ``AxisType.Auto``
axes.  Callsites build meshes, shardings and shard_maps through these
helpers so the repo's conventions (Auto axes, the ``check`` spelling,
manual-axis subsets) live in one file.
"""
from __future__ import annotations

import jax


def make_mesh(shape, names):
    """``jax.make_mesh`` with every axis explicitly Auto."""
    shape, names = tuple(shape), tuple(names)
    return jax.make_mesh(
        shape, names, axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def mesh_from_devices(device_grid, names):
    """``jax.sharding.Mesh`` over an explicit device array, Auto axes."""
    names = tuple(names)
    return jax.sharding.Mesh(
        device_grid, names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def named_sharding(mesh, spec) -> jax.sharding.NamedSharding:
    """``NamedSharding`` construction (callsites build shardings without
    importing jax.sharding directly)."""
    return jax.sharding.NamedSharding(mesh, spec)


def shard_map(fn, mesh, in_specs, out_specs, check: bool = False,
              axis_names=None):
    """``jax.shard_map``; ``check`` maps onto ``check_vma``.
    ``axis_names`` is the *manual* axis set; ``None`` means manual over
    every mesh axis."""
    kwargs = {"check_vma": check}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
