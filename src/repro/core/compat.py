"""The sharded entry points' one view of the JAX mesh API.

The sharded wrappers (core.halo callers, train sharding, the distributed
tests) route through here, so a change of the runtime's sharding surface is
met in one place.  Single-shard code paths never import these symbols at
call time, preserving the paper's portability discipline: the same
application source runs on whatever runtime is underneath.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["shard_map", "make_mesh"]


def shard_map(f, **kwargs):
    """``jax.shard_map`` with the varying-manual-axes check off by default.

    The check needs every primitive in the body to state which mesh axes
    its outputs vary over; a ``pallas_call`` builds its outputs from plain
    ``ShapeDtypeStruct``s that carry none, so with the check on every fused
    launch inside a shard fails to trace.  The drivers' bodies are written
    per shard (explicit halo exchange, explicit psums), which is exactly
    what the unchecked mode assumes."""
    kwargs.setdefault("check_vma", False)
    return jax.shard_map(f, **kwargs)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with every axis Auto — the implicit sharding the
    sharded wrappers assume."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), axis_names,
        axis_types=(AxisType.Auto,) * len(axis_names),
    )
