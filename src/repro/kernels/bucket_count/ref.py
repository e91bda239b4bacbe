"""Pure-jnp bucket count: the scatter-add the MXU kernel replaces."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def scatter_counts(bucket: jax.Array, Q: int, dtype=jnp.int32) -> jax.Array:
    """bucket: [..., T] keys in [0, Q) -> [..., Q] counts in ``dtype``."""
    count = lambda b: jnp.zeros((Q,), dtype).at[b].add(1)
    for _ in range(bucket.ndim - 1):
        count = jax.vmap(count)
    return count(bucket)
