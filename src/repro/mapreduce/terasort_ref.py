"""Plain NumPy reference of :func:`repro.mapreduce.jobs.terasort_job`.

It shares no code with the job: one ``np.lexsort`` of every record of the
input by (k0, k1, k2, row id), unsigned, a split of the sorted records by
key range (the top log2(Q) bits of k0), and per partition the summary the
reducer emits -- record count, overflow (what the map's blocks held beyond
``capacity``), first and last key, adjacent pairs out of order, and the
checksum sum_i (i + 1) h(rec_i) mod 2^32 over the sorted order, with
h(rec) = sum_w (0x9E3779B1 (2w + 1) mod 2^32) word_w mod 2^32.
"""
from __future__ import annotations

import numpy as np

WORDS = 25
COEFFS = np.asarray([(0x9E3779B1 * (2 * w + 1)) % 2**32
                     for w in range(WORDS)], np.uint64)


def terasort_summary(subfiles: np.ndarray, Q: int,
                     capacity: int) -> np.ndarray:
    """[Q, 10] int32 summary of the records of ``subfiles`` ([N, n * 25]
    int32, subfile-major), the sort's answer for every partition."""
    N = subfiles.shape[0]
    rec = np.ascontiguousarray(subfiles).reshape(-1, WORDS).view(np.uint32)
    n = rec.shape[0] // N
    shift = 32 - (Q.bit_length() - 1)
    bucket = (rec[:, 0] >> np.uint32(shift)).astype(np.int64)
    blocks = np.bincount(np.repeat(np.arange(N), n) * Q + bucket,
                         minlength=N * Q).reshape(N, Q)
    overflow = np.maximum(blocks - capacity, 0).sum(axis=0)
    order = np.lexsort((rec[:, 3], rec[:, 2], rec[:, 1], rec[:, 0]))
    srt = rec[order].astype(np.uint64)
    h = (srt * COEFFS).sum(axis=1) % 2**32
    bounds = np.searchsorted(bucket[order], np.arange(Q + 1))
    out = np.zeros((Q, 10), np.int64)      # sorted: no pair out of order
    for q in range(Q):
        a, b = bounds[q], bounds[q + 1]
        part = srt[a:b]
        out[q, 0], out[q, 1] = b - a, overflow[q]
        if b > a:
            out[q, 2:5], out[q, 5:8] = part[0, :3], part[-1, :3]
        out[q, 9] = int((np.arange(1, b - a + 1, dtype=np.uint64)
                         * h[a:b]).sum() % 2**32)
    return out.astype(np.uint32).view(np.int32)
