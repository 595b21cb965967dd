"""Thin spellings of the JAX mesh APIs the solver uses.

``shard_map`` takes the solver's ``check`` flag as ``check_vma``, and
``make_mesh`` builds meshes with Auto axis types (explicit sharding is
opt-in per axis in current JAX).
"""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
