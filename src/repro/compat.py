"""The two JAX mesh entry points this repo uses, with its fixed settings.

``make_mesh`` gives every axis the Auto axis type, and ``shard_map`` turns
off the varying-manual-axes check (the per-rank bodies mix replicated and
sharded values freely).
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with explicit Auto axis types."""
    types = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=types,
                         devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma`` off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
