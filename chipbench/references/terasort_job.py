"""Plain NumPy reference of HiBench TeraSort (``terasort_job``), checked
TeraValidate-style.

Records are TeraGen's, 100 bytes as 25 int32 words: words 0-2 the 80-bit
key, big-endian and compared unsigned (the low 16 bits of word 2 are the
two zero break bytes), word 3 the global row id, words 4-24 filler.  Job
k's answer is, per reduce partition q (the keys whose top log2(Q) bits are
q), ten int32 numbers: the record count, the overflow (what the map's
per-(subfile, partition) blocks held beyond ``capacity``), the first and
last key of the partition in sorted order (3 words each), the number of
adjacent pairs out of order, and the checksum sum_i (i + 1) h(rec_i) mod
2^32 over the partition in order by (key, row id), with h(rec) = sum_w
c_w word_w mod 2^32, c_w = 0x9E3779B1 (2w + 1) mod 2^32.  It shares no
code with the engine or its jobs.

Job k's records are the base records with k0 += k 0x9E3779B9 mod 2^32.
That shift turns the circle of k0 values, so job k's sorted order is the
base order rotated at one point, and h moves by c_0 k 0x9E3779B9 on every
record: ``prepare`` sorts the base once, and ``expected`` reads each job
off prefix sums with no sort.
"""
from __future__ import annotations

import numpy as np

WORDS = 25
PHI = 0x9E3779B9
COEFFS = [(0x9E3779B1 * (2 * w + 1)) % 2**32 for w in range(WORDS)]
M32 = 2**32


def _sizes(cfg: dict):
    n, Q = cfg["job_args"]["records_per_subfile"], cfg["Q"]
    if (cfg["tokens_per_subfile"] != n * WORDS or cfg["job_args"]["Q"] != Q
            or cfg["token_range"] != 2**31 or Q < 2 or Q & (Q - 1)):
        raise ValueError("terasort_job reference needs tokens_per_subfile "
                         "= 25 records_per_subfile, token_range = 2^31 and "
                         "Q a power of two")
    return cfg["N"], n, Q, cfg["job_args"]["capacity"]


def job_input(base: np.ndarray, k: int, cfg: dict) -> np.ndarray:
    """A new [N, n * 25] int32 array of records from the harness's 31-bit
    words: k2 is word 2's low 16 bits moved to the top, k0 and k1 are
    words 0 and 1 with bits 16 and 17 of word 2 as their top bits (so the
    80-bit key is uniform), word 3 the row id; then k0 += k PHI mod 2^32.
    The filler is the base's own words."""
    N, n, _, _ = _sizes(cfg)
    b = base.view(np.uint32).reshape(N, n, WORDS)
    x = np.empty_like(base)
    r = x.view(np.uint32).reshape(N, n, WORDS)
    r[..., 4:] = b[..., 4:]
    head = b[..., :3].copy()
    top = np.uint32(0x80000000)
    k0 = head[..., 0] | ((head[..., 2] << np.uint32(15)) & top)
    k0 += np.uint32(k * PHI % M32)
    k1 = head[..., 1] | ((head[..., 2] << np.uint32(14)) & top)
    r[..., :4] = np.stack(
        [k0, k1, head[..., 2] << np.uint32(16),
         np.arange(N * n, dtype=np.uint32).reshape(N, n)], axis=-1)
    return x


def _hash(rec: np.ndarray) -> np.ndarray:
    """h of each row of a [m, 25] uint32 array, as uint64 below 2^32."""
    h = np.zeros(len(rec), np.uint64)
    for w, c in enumerate(COEFFS):
        h += rec[:, w].astype(np.uint64) * np.uint64(c)
    return h % np.uint64(M32)


def _summary(rec: np.ndarray, order: np.ndarray, bucket: np.ndarray,
             N: int, Q: int, C: int) -> np.ndarray:
    """The summary of each partition with its records taken in ``order``
    (an order that groups the partitions, ascending)."""
    n = len(rec) // N
    blocks = np.bincount(np.repeat(np.arange(N), n) * Q + bucket,
                         minlength=N * Q).reshape(N, Q)
    s = rec[order]
    bs = bucket[order]
    bounds = np.searchsorted(bs, np.arange(Q + 1))
    a, b = s[:-1, :4], s[1:, :4]
    less = b[:, 3] < a[:, 3]
    for w in (2, 1, 0):
        less = (b[:, w] < a[:, w]) | ((b[:, w] == a[:, w]) & less)
    h = _hash(s)
    out = np.zeros((Q, 10), np.int64)
    out[:, 1] = np.maximum(blocks - C, 0).sum(axis=0)
    out[:, 8] = np.bincount(bs[1:][less & (bs[1:] == bs[:-1])],
                            minlength=Q)
    for q in range(Q):
        lo, hi = bounds[q], bounds[q + 1]
        out[q, 0] = hi - lo
        if hi > lo:
            out[q, 2:5], out[q, 5:8] = s[lo, :3], s[hi - 1, :3]
        pos = np.arange(1, hi - lo + 1, dtype=np.uint64)
        out[q, 9] = int((pos * h[lo:hi]).sum() % np.uint64(M32))
    return out.astype(np.uint32).view(np.int32)


def _parts(x: np.ndarray, cfg: dict):
    N, n, Q, C = _sizes(cfg)
    rec = x.view(np.uint32).reshape(N * n, WORDS)
    bucket = (rec[:, 0] >> np.uint32(32 - (Q.bit_length() - 1))
              ).astype(np.int64)
    return rec, bucket, N, Q, C


def direct(x: np.ndarray, cfg: dict) -> np.ndarray:
    """The answer of one input array from scratch: one lexsort of all its
    records by (k0, k1, k2, row id), split by key range."""
    rec, bucket, N, Q, C = _parts(x, cfg)
    order = np.lexsort((rec[:, 3], rec[:, 2], rec[:, 1], rec[:, 0]))
    return _summary(rec, order, bucket, N, Q, C)


def control(base: np.ndarray, k: int, cfg: dict) -> np.ndarray:
    """The same summary with each partition's records left in map order
    (subfile by subfile, unsorted): what a sort that only partitions
    would hand on."""
    rec, bucket, N, Q, C = _parts(job_input(base, k, cfg), cfg)
    return _summary(rec, np.argsort(bucket, kind="stable"), bucket, N, Q, C)


def prepare(base: np.ndarray, cfg: dict) -> dict:
    """Job 0 sorted once: its sorted key words, prefix sums of h and of
    j h(j) over the sorted order, and each subfile's sorted k0."""
    N, n, Q, C = _sizes(cfg)
    rec = job_input(base, 0, cfg).view(np.uint32).reshape(N * n, WORDS)
    order = np.lexsort((rec[:, 3], rec[:, 2], rec[:, 1], rec[:, 0]))
    keys = rec[order, :3].astype(np.int64)
    h = _hash(rec)[order]
    zero = np.zeros(1, np.uint64)
    return {
        "keys": keys,
        "p0": np.concatenate([zero, np.cumsum(h)]),       # wraps mod 2^64
        "p1": np.concatenate([zero, np.cumsum(
            np.arange(N * n, dtype=np.uint64) * h)]),
        "sub_k0": np.sort(rec[:, 0].reshape(N, n), axis=1).astype(np.int64),
    }


def expected(state: dict, k: int, cfg: dict) -> np.ndarray:
    """Job k's answer from the sorted base in O(Q N log n): no sort."""
    N, n, Q, C = _sizes(cfg)
    c = k * PHI % M32
    low = (M32 - c) % M32          # base k0 >= low wraps to the front
    t = np.arange(Q + 1, dtype=np.int64) * (M32 // Q)

    def below(k0, t):
        """How many of the sorted base values ``k0`` lie below ``t`` once
        shifted by c, and where the wrapped run starts."""
        w = np.searchsorted(k0, low)
        return (np.searchsorted(k0, np.minimum(low + t, M32)) - w
                + np.minimum(np.searchsorted(k0, np.maximum(t - c, 0)), w)
                ), w

    keys, p0, p1 = state["keys"], state["p0"], state["p1"]
    m = len(keys)
    bounds, w = below(keys[:, 0], t)
    w = int(w)
    over = sum(np.maximum(np.diff(below(k0, t)[0]) - C, 0)
               for k0 in state["sub_k0"])
    delta = COEFFS[0] * c % M32
    out = np.zeros((Q, 10), np.int64)
    for q in range(Q):
        lo, hi = int(bounds[q]), int(bounds[q + 1])
        size = hi - lo
        out[q, 0], out[q, 1] = size, over[q]
        if not size:
            continue
        first, last = (lo + w) % m, (hi - 1 + w) % m
        out[q, 2:5], out[q, 5:8] = keys[first], keys[last]
        out[q, 2] = (keys[first, 0] + c) % M32
        out[q, 5] = (keys[last, 0] + c) % M32
        # the partition is base [first, first + l1) then [0, size - l1)
        l1 = min(size, m - first)
        s = delta * size * (size + 1) // 2
        for u, v, off in ((first, first + l1, 0), (0, size - l1, l1)):
            s0, s1 = int(p0[v]) - int(p0[u]), int(p1[v]) - int(p1[u])
            s += s1 + (off + 1 - u) * s0
        out[q, 9] = s % M32
    return out.astype(np.uint32).view(np.int32)
