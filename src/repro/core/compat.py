"""Mesh and shard_map helpers shared by every sharded code path."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def shard_map(f, *, mesh, in_specs, out_specs, axis_names):
    """``jax.shard_map`` returned jit-wrapped.

    Eager partial-manual ``shard_map`` (``axis_names`` a strict subset of
    the mesh, e.g. ``{"data"}`` on a ``("data", "model")`` mesh) refuses an
    ``out_specs=P()`` that the jitted form accepts, and jit also reshards
    operands committed to a single device onto the mesh. Nested jit is a
    no-op for callers that already jit."""
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, axis_names=axis_names,
                                 check_vma=False))
