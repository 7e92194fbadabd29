"""Thin wrappers that pin the jax mesh/shard_map calling conventions the
engine relies on, so call sites never repeat them.

The only behaviour beyond the plain jax API: ``shard_map`` disables the
varying-manual-axes check (``check_vma``), which the engine's collectives
violate intentionally (per-shard scalars are returned unreplicated), and
``make_mesh`` builds every axis as ``AxisType.Auto``.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "set_mesh", "shard_map"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def set_mesh(mesh):
    """``with set_mesh(mesh):`` — the ambient-mesh context."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
