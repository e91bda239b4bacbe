"""Concrete MapReduce jobs used by the examples, tests and benchmarks."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.bucket_count.ops import bucket_counts
from .engine import MapReduceJob


def histogram_job(vocab_hash_mod: int = 2**16) -> MapReduceJob:
    """WordCount-style: subfile = int32 token array; key = token bucket;
    value = occurrence count in the subfile.  Reduce = total count."""
    def map_fn(tokens: jax.Array, Q: int) -> jax.Array:
        bucket = (tokens.astype(jnp.uint32) % jnp.uint32(Q)).astype(jnp.int32)
        counts = bucket_counts(bucket, Q)
        return counts[:, None].astype(jnp.float32)          # [Q, 1]

    def reduce_fn(vals: jax.Array) -> jax.Array:            # [N, 1]
        return vals.sum(axis=0)

    return MapReduceJob("histogram", 1, map_fn, reduce_fn)


def groupby_mean_job() -> MapReduceJob:
    """Group-by-key mean: subfile = [n, 2] (key_src, value) rows; emits
    per-bucket (sum, count); reduce = global mean per bucket."""
    def map_fn(rows: jax.Array, Q: int) -> jax.Array:
        keys = (rows[:, 0].astype(jnp.uint32) % jnp.uint32(Q)).astype(jnp.int32)
        vals = rows[:, 1].astype(jnp.float32)
        s = jnp.zeros((Q,), jnp.float32).at[keys].add(vals)
        c = jnp.zeros((Q,), jnp.float32).at[keys].add(1.0)
        return jnp.stack([s, c], axis=-1)                    # [Q, 2]

    def reduce_fn(vals: jax.Array) -> jax.Array:             # [N, 2]
        s, c = vals[:, 0].sum(), vals[:, 1].sum()
        return jnp.stack([s / jnp.maximum(c, 1.0), c])

    return MapReduceJob("groupby_mean", 2, map_fn, reduce_fn)


def wide_histogram_job(d: int, dtype=jnp.float32) -> MapReduceJob:
    """Histogram with a width-d payload per (key, subfile): counts scaled by
    a fixed integer weight vector.  Integer-valued throughout, so every
    execution path (including coded multicast encode/decode) is bit-exact —
    the shuffle-bound workload of ``benchmarks/pipeline_bench``.  An integer
    ``dtype`` admits the GF(2) ``multicast='coded_xor'`` wire format.
    """
    def map_fn(tokens: jax.Array, Q: int) -> jax.Array:
        bucket = (tokens.astype(jnp.uint32) % jnp.uint32(Q)).astype(jnp.int32)
        counts = bucket_counts(bucket, Q, dtype)
        w = (jnp.arange(d, dtype=dtype) % 7) + 1
        return counts[:, None] * w[None, :]                  # [Q, d]

    def reduce_fn(vals: jax.Array) -> jax.Array:             # [N, d]
        return vals.sum(axis=0)

    suffix = "" if dtype == jnp.float32 else f"_{jnp.dtype(dtype).name}"
    return MapReduceJob(f"wide_histogram_d{d}{suffix}", d, map_fn, reduce_fn)


def terasort_bucket_job(key_space: int = 2**20,
                        payload_quantiles: int = 8) -> MapReduceJob:
    """TeraSort bucketing phase (cf. CodedTeraSort [Li et al., 2017]): each
    reducer owns a contiguous key range; mappers emit, per range, the count
    and a fixed set of quantile summaries of their records landing in it.
    (The in-bucket sort is reducer-local compute, not shuffle traffic, so the
    shuffle cost model is exactly the paper's.)"""
    def map_fn(records: jax.Array, Q: int) -> jax.Array:
        rec = records.astype(jnp.float32)
        edges = jnp.linspace(0.0, float(key_space), Q + 1)
        bucket = jnp.clip(jnp.searchsorted(edges, rec, side="right") - 1,
                          0, Q - 1)
        counts = jnp.zeros((Q,), jnp.float32).at[bucket].add(1.0)
        sums = jnp.zeros((Q,), jnp.float32).at[bucket].add(rec)
        mins = jnp.full((Q,), jnp.inf).at[bucket].min(rec)
        maxs = jnp.full((Q,), -jnp.inf).at[bucket].max(rec)
        feats = [counts, sums, jnp.where(jnp.isfinite(mins), mins, 0.0),
                 jnp.where(jnp.isfinite(maxs), maxs, 0.0)]
        extra = payload_quantiles - len(feats)
        for k in range(max(extra, 0)):
            feats.append(counts * 0.0)
        return jnp.stack(feats[:payload_quantiles], axis=-1)  # [Q, pq]

    def reduce_fn(vals: jax.Array) -> jax.Array:              # [N, pq]
        counts = vals[:, 0].sum()
        sums = vals[:, 1].sum()
        mn = vals[:, 2].min()
        mx = vals[:, 3].max()
        return jnp.stack([counts, sums, mn, mx])

    return MapReduceJob("terasort_bucket", payload_quantiles, map_fn,
                        reduce_fn)
