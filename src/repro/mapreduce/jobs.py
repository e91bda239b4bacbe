"""Concrete MapReduce jobs used by the examples, tests and benchmarks."""
from __future__ import annotations

import numpy as np

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


# TeraSort records: one 100-byte TeraGen record is 25 int32 words --
# words 0-2 the 10-byte key (80 bits big-endian, compared unsigned; the low
# 16 bits of word 2 are TeraGen's two zero break bytes), word 3 the
# record's global row id (non-negative, unique in a job), words 4-24 filler
TERA_WORDS = 25
# h(rec) = sum_w TERA_HASH[w] * word_w mod 2^32, fixed odd constants
TERA_HASH = tuple((0x9E3779B1 * (2 * w + 1)) % 2**32
                  for w in range(TERA_WORDS))


def _lex_less(b, a):
    """Elementwise b < a, lexicographic over lists of uint32 arrays."""
    less = b[-1] < a[-1]
    for x, y in zip(b[-2::-1], a[-2::-1]):
        less = (x < y) | ((x == y) & less)
    return less


def _transposition_sort(cols, idx):
    """Odd-even transposition sort of ``idx`` by the lexicographic key
    ``cols``, run until a round swaps nothing.  From an order that is
    already right but for runs of tied sort keys it takes about as many
    rounds as the longest such run; from any order it is exact."""
    m = idx.shape[0]
    parity = jnp.arange(m - 1) % 2
    no = jnp.zeros((1,), bool)

    def phase(arrs, p):
        swap = _lex_less([c[1:] for c in arrs[:-1]],
                         [c[:-1] for c in arrs[:-1]]) & (parity == p)
        down = jnp.concatenate([swap, no])
        up = jnp.concatenate([no, swap])

        def move(x):
            return jnp.where(down, jnp.concatenate([x[1:], x[-1:]]),
                             jnp.where(up, jnp.concatenate([x[:1], x[:-1]]),
                                       x))
        return [move(x) for x in arrs], jnp.any(swap)

    def body(carry):
        arrs, _ = carry
        arrs, even = phase(arrs, 0)
        arrs, odd = phase(arrs, 1)
        return arrs, even | odd

    # the first test is True, typed like the data (inside shard_map it
    # carries the data's varying mesh axes)
    arrs, _ = jax.lax.while_loop(lambda c: c[1], body,
                                 ([*cols, idx], idx[0] == idx[0]))
    return arrs[-1]


def _packed_order(hi: jax.Array, bits: int) -> jax.Array:
    """The order of (hi, position) for ``hi`` below 2^(32 - bits): one
    one-operand sort of both packed in a uint32."""
    size = hi.shape[0]
    key = (hi << bits) | jax.lax.iota(jnp.uint32, size)
    return (jax.lax.sort(key) & jnp.uint32((1 << bits) - 1)
            ).astype(jnp.int32)


def _record_order(words, live: jax.Array, s: int) -> jax.Array:
    """The order of a reducer's record slots by (k0, k1, k2, row id)
    unsigned, valid slots first: one packed sort of the key bits below the
    partition's top ``s`` (as many as fit beside the slot index), then an
    odd-even transposition sort by the whole key and row id for the runs
    tied on the packed bits."""
    k0, k1, k2, rid = words
    m = live.shape[0]
    bits = max((m - 1).bit_length(), 1)
    if bits > 24:
        raise ValueError(f"terasort_job: {m} reducer slots is too many")
    # empty slots last: valid records' packed bits stop one short
    top = jnp.uint32((1 << (32 - bits)) - 1)
    hi = ((k0 << s) | (k1 >> (32 - s))) >> bits
    order = _packed_order(jnp.where(live, jnp.minimum(hi, top - 1), top),
                          bits)
    cols = [(~live[order]).astype(jnp.uint32), k0[order], k1[order],
            k2[order], rid[order]]
    return _transposition_sort(cols, order)


def terasort_job(records_per_subfile: int, Q: int,
                 capacity: int) -> MapReduceJob:
    """HiBench TeraSort: a total-order sort of TeraGen records by
    (key, row id), checked TeraValidate-style.

    Reducer key q owns the key range whose top s = log2(Q) bits are q (Q a
    power of two).  The map partitions a subfile of ``records_per_subfile``
    records (a flat int32 array of 25 words each) into Q padded blocks of
    ``capacity`` record slots: one sort of (bucket, position), packed in
    one uint32, then one row gather into slot (q, c) for c < count[q],
    with unused slots zero -- no scatter.  Its [Q, d] row for key q is the
    block's ``capacity * 25`` words, then the block's true count,
    zero-padded to d, a multiple of 128 lanes.

    The reducer masks its N blocks by their counts, orders the valid
    records by (k0, k1, k2, row id) unsigned (:func:`_record_order`), and
    gathers the rows into that order, all under the ``reducer_sort``
    scope.  Per partition it emits TeraValidate's fields as ten int32:
    record count, overflow, first key (3 words), last key (3 words),
    adjacent pairs out of order, and the checksum.  The program's sorts are
    one-operand: XLA's TPU sort compiles in about 15 s per op past a few
    thousand elements, and several times that with more operands.  The
    reducer sorts min(count, capacity) records of each block and reports
    what the blocks held beyond capacity as the overflow, so a block that
    overflowed shows in the count and the checksum rather than vanishing.
    The checksum, sum_i (i + 1) h(rec_i) mod 2^32 over the sorted order,
    depends on the whole permutation.
    """
    W, C, n = TERA_WORDS, capacity, records_per_subfile
    if Q < 2 or Q & (Q - 1):
        raise ValueError(f"terasort_job needs Q a power of two >= 2; Q={Q}")
    s = Q.bit_length() - 1
    d = -(-(C * W + 1) // 128) * 128
    coeffs = np.asarray(TERA_HASH, np.uint32)
    u32 = jnp.uint32
    map_bits = max((n - 1).bit_length(), 1)
    if s + map_bits > 32:
        raise ValueError("terasort_job: log2(Q) + log2(records) > 32")

    def map_fn(sub: jax.Array, Q_: int) -> jax.Array:
        if Q_ != Q:
            raise ValueError(f"terasort_job built for Q={Q}, run at {Q_}")
        rec = sub.reshape(n, W)
        bucket = rec[:, 0].astype(u32) >> (32 - s)
        order = _packed_order(bucket, map_bits)
        counts = jnp.sum(bucket[None, :] == jnp.arange(Q, dtype=u32)[:, None],
                         axis=1, dtype=jnp.int32)                 # [Q]
        starts = jnp.cumsum(counts) - counts
        slot = jnp.arange(C)
        src = order[jnp.minimum(starts[:, None] + slot, n - 1)]  # [Q, C]
        rows = jnp.where((slot < counts[:, None])[..., None], rec[src], 0)
        return jnp.concatenate(
            [rows.reshape(Q, C * W), counts[:, None],
             jnp.zeros((Q, d - C * W - 1), jnp.int32)], axis=1)   # [Q, d]

    def reduce_fn(vals: jax.Array) -> jax.Array:              # [N, d]
        counts = vals[:, C * W]
        held = jnp.minimum(counts, C)
        overflow = jnp.sum(jnp.maximum(counts - C, 0))
        recs = vals[:, :C * W].reshape(-1, W)                  # [N*C, W]
        m = recs.shape[0]
        live = (jnp.arange(C)[None, :] < held[:, None]).reshape(-1)
        words = [jax.lax.bitcast_convert_type(recs[:, w], u32)
                 for w in range(4)]
        with jax.named_scope("reducer_sort"):
            srt = recs[_record_order(words, live, s)]         # sorted rows
        total = jnp.sum(held)
        pos = jnp.arange(m)
        su = jax.lax.bitcast_convert_type(srt, u32)
        h = jnp.sum(su * coeffs, axis=1, dtype=u32)
        checksum = jnp.sum(jnp.where(pos < total,
                                     (pos + 1).astype(u32) * h, 0),
                           dtype=u32)
        less = _lex_less([su[1:, w] for w in range(4)],
                         [su[:-1, w] for w in range(4)])
        disorder = jnp.sum(less & (pos[1:] < total), dtype=jnp.int32)
        some = total > 0
        first = jnp.where(some, srt[0, :3], 0)
        last = jnp.where(some, srt[jnp.maximum(total - 1, 0), :3], 0)
        return jnp.concatenate([
            jnp.stack([total, overflow]), first, last,
            jnp.stack([disorder,
                       jax.lax.bitcast_convert_type(checksum, jnp.int32)])])

    def record_slots(shape: tuple) -> tuple:
        records = shape[0] * (shape[1] // W)
        return records, max(shape[0] * Q * C - records, 0)

    return MapReduceJob(f"terasort_n{n}_q{Q}_c{C}", d, map_fn, reduce_fn,
                        record_slots)
