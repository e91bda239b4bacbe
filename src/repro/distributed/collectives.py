"""Hierarchical (rack-aware) collectives — the paper's two-stage shuffle
mapped onto TPU mesh axes.

The paper's insight is that a two-level network (fast ToR / slow root)
wants shuffles decomposed into a slow-tier stage at 1/r the volume and a
fast-tier stage that absorbs the residual.  On a multi-pod TPU mesh the
same decomposition applies with pod = rack:

  * :func:`hierarchical_all_to_all`  — MoE expert dispatch in two stages:
    tokens first move to the destination pod's matching slot (one bundled
    slow-axis a2a), then to the destination expert inside the pod (fast
    axis).  Slow-axis message count drops from K-1 distinct flows per chip
    to P-1 bundled flows (the paper's L_cro vs L_tot split for shuffles
    that are not sum-reducible).
  * :func:`hierarchical_psum` / :func:`hierarchical_psum_scatter` — the
    SUM-reducible case (gradients): intra-pod reduce-scatter, cross-pod
    all-reduce on 1/Kr shards, intra-pod all-gather.  Combined with map
    replication r over pods, the cross-pod stage vanishes entirely for
    replicated chunks (see repro.core.gradient_sync).

All functions are shard_map-level (named-axis) collectives.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def hierarchical_psum(x: jax.Array, fast_axis: str, slow_axis: str,
                      scatter_dim: int = 0) -> jax.Array:
    """All-reduce over (fast x slow) with the slow stage at 1/Kr volume."""
    x = jax.lax.psum_scatter(x, fast_axis, scatter_dimension=scatter_dim,
                             tiled=True)
    x = jax.lax.psum(x, slow_axis)
    return jax.lax.all_gather(x, fast_axis, axis=scatter_dim, tiled=True)


def hierarchical_psum_scatter(x: jax.Array, fast_axis: str, slow_axis: str,
                              scatter_dim: int = 0) -> jax.Array:
    """Reduce-scatter over both tiers (result sharded over fast axis)."""
    x = jax.lax.psum_scatter(x, fast_axis, scatter_dimension=scatter_dim,
                             tiled=True)
    return jax.lax.psum(x, slow_axis)


def hierarchical_all_to_all(x: jax.Array, fast_axis: str, slow_axis: str,
                            *, split_axis: int = 0, concat_axis: int = 0,
                            ) -> jax.Array:
    """Two-stage all-to-all over a (slow, fast) product of axes.

    x: [..., n_slow * n_fast, ...] along ``split_axis`` — one slice per
    global destination, ordered slow-major (destination pod, then in-pod
    slot, matching the mesh's device order).

    Stage 1 bundles all slices bound for pod p into ONE slow-axis message
    (the paper's multicast-bundling of the cross-rack stage); stage 2
    delivers within the pod on fast links.  Equivalent to a flat
    all_to_all over the joint axis (asserted in tests), but the slow tier
    carries each byte exactly once in 1 bundled flow instead of Kr
    distinct flows — the schedule the roofline's cross-pod term wants.
    """
    n_slow = jax.lax.axis_size(slow_axis)
    n_fast = jax.lax.axis_size(fast_axis)
    n = x.shape[split_axis]
    assert n == n_slow * n_fast, (n, n_slow, n_fast)

    # reshape split axis -> (n_slow, n_fast)
    shape = list(x.shape)
    shape[split_axis:split_axis + 1] = [n_slow, n_fast]
    xs = x.reshape(shape)
    # stage 1: cross-pod exchange of pod-bundles (slow tier, bundled)
    xs = jax.lax.all_to_all(xs, slow_axis, split_axis=split_axis,
                            concat_axis=split_axis, tiled=False)
    # xs now has, at this pod, the bundle from every source pod; in-pod slot
    # axis is still the destination slot -> stage 2 on the fast tier
    xs = jax.lax.all_to_all(xs, fast_axis, split_axis=split_axis + 1,
                            concat_axis=split_axis + 1, tiled=False)
    # collapse (n_slow src-pods, n_fast src-slots) back into one axis
    shape = list(xs.shape)
    shape[split_axis:split_axis + 2] = [n]
    out = xs.reshape(shape)
    if concat_axis != split_axis:
        out = jnp.moveaxis(out, split_axis, concat_axis)
    return out


def flat_all_to_all(x: jax.Array, fast_axis: str, slow_axis: str, *,
                    split_axis: int = 0, concat_axis: int = 0) -> jax.Array:
    """Baseline: single all_to_all over the joint (slow, fast) axis."""
    return jax.lax.all_to_all(x, (slow_axis, fast_axis),
                              split_axis=split_axis,
                              concat_axis=concat_axis, tiled=False)


def coded_cross_pod_allreduce(chunk_grads: jax.Array, slow_axis: str,
                              P_: int, failed: Optional[int] = None,
                              ) -> jax.Array:
    """Convenience re-export of the r=2 coded reduce-scatter + all-gather
    over the slow axis (see repro.core.gradient_sync for the scheme)."""
    from ..core.gradient_sync import coded_reduce_scatter_r2
    shard = coded_reduce_scatter_r2(chunk_grads, slow_axis, P_,
                                    failed=failed)
    return jax.lax.all_gather(shard, slow_axis, axis=0, tiled=True)
