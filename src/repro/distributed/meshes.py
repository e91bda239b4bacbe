"""Mesh construction for the repo's named-axis programs.

The shard_map programs here (shuffle, fused engine, trainer sync) place
their own inputs and name their own partition specs, so their meshes use
Auto axis types rather than ``jax.make_mesh``'s Explicit default, under
which jit would also type every intermediate's sharding.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: tuple, names: tuple) -> Mesh:
    """``jax.make_mesh`` over the default devices with Auto axis types."""
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(names))
