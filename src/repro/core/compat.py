"""The repo's one place for JAX mesh and shard_map conventions.

Import from here everywhere:

    from repro.core.compat import shard_map          # jax.shard_map
    from repro.core.compat import shard_map_norep    # checks disabled
    from repro.core.compat import make_mesh          # Auto axis types

Every mesh in the repo is built by :func:`make_mesh`, which pins all axes
to ``AxisType.Auto``: the shard_map paths place their operands through
explicit ``in_specs``/``out_specs`` and leave everything outside the
shard_map to the compiler's sharding propagation.  Under jax's default of
Explicit axes, host-side scatters on sharded outputs (``packed_to_dense``)
would have to name their output sharding.
"""
from __future__ import annotations

import jax
from jax import shard_map
from jax.sharding import AxisType

__all__ = ["shard_map", "shard_map_norep", "make_mesh", "axis_size",
           "cost_analysis_dict"]

axis_size = jax.lax.axis_size


def cost_analysis_dict(compiled) -> dict:
    """compiled.cost_analysis() as a dict ({} for empty programs)."""
    return compiled.cost_analysis() or {}


def shard_map_norep(f, *, mesh, in_specs, out_specs):
    """shard_map with replication checking off -- required for bodies that
    contain pallas_call, which has no replication rule."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """jax.make_mesh with every axis pinned to ``AxisType.Auto``."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)
