"""Pallas TPU kernel: count keys into Q bins on the MXU.

A histogram with 0/1 weights is a matmul of one-hot matrices.  Split each
key q into digits q = 128*h + l; the count table C[h, l] is then
OneHot_h(ids)^T . OneHot_l(ids), contracted over the ids, and the counts
are C flattened to [Hn*128] and cut to Q.  Both one-hots are built in
VMEM on the VPU and never touch HBM; the MXU sums them.

Tiling: the ids of a batch row are laid out [T/128, 128] (lane-major, as
stored).  The grid is (batch row, id block) with the id axis sequential;
the output block [Hn, 128] int32 (Hn = ceil(Q/128) rounded up to 8) stays
resident across it.  Inside a block the ids go by groups of 32 rows
(4096 ids): the group's rows side by side along the lanes give
Ht [Hn, 4096] = (iota_sublane == id >> 7) and Lt [128, 4096] =
(iota_sublane == id & 127) in bf16, and the NT matmul Ht . Lt^T (the
contraction over the lane axis of both, as flash attention's q.k^T) adds
the group's [Hn, 128] counts to an f32 accumulator.  On a v5e a group of
32 rows counts 2^25 ids in 4.3 ms, one of 8 rows in 6.8 ms: the wider
contraction pays the MXU's per-matmul cost less often.

Exact for any key skew: products are 0/1, an f32 accumulator holds the
counts of one block of at most BLOCK_ROWS*128 = 2^15 ids (exact up to
2^24), and blocks add in int32.  Ids past the end of the row (the tail of
the last block) become -1, whose high digit matches no row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
GROUP_ROWS = 32                 # rows of 128 ids in one one-hot group
BLOCK_ROWS = 256                # rows of 128 ids in one grid step
VMEM_BUDGET = 12 * 2**20        # of the 16 MiB default scoped VMEM of a v5e


def vmem_bytes(hn: int) -> int:
    """VMEM working set of the kernel at Hn rows, every value of a group
    held at once (an upper bound: Mosaic holds less)."""
    group = GROUP_ROWS * LANES
    ids = 2 * BLOCK_ROWS * LANES * 4            # double-buffered id block
    table = 3 * hn * LANES * 4                  # output (x2) + accumulator
    onehots = (hn + LANES) * group * (4 + 2)    # compare result + bf16
    return ids + table + onehots


# the largest Q whose one-hot group and count table fit VMEM_BUDGET
MAX_Q = LANES * max(h for h in range(8, 4096, 8)
                    if vmem_bytes(h) <= VMEM_BUDGET)


def _count_kernel(ids_ref, o_ref, *, hn: int, block_rows: int, n_rows: int):
    """ids: [block_rows, 128] int32 keys; o: [hn, 128] int32 counts."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    group = GROUP_ROWS * LANES
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (hn, group), 0)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, group), 0)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (GROUP_ROWS, LANES), 0)

    def body(g, acc):
        r0 = pl.multiple_of(g * GROUP_ROWS, GROUP_ROWS)
        x = ids_ref[pl.ds(r0, GROUP_ROWS), :]           # [GROUP_ROWS, 128]
        if n_rows % block_rows:
            x = jnp.where(t * block_rows + r0 + row_iota < n_rows, x, -1)
        hi, lo = x >> 7, x & (LANES - 1)
        # the group's rows of ids side by side along the lanes: [1, group]
        hi = jnp.concatenate([hi[r:r + 1] for r in range(GROUP_ROWS)], 1)
        lo = jnp.concatenate([lo[r:r + 1] for r in range(GROUP_ROWS)], 1)
        ht = (hi_iota == hi).astype(jnp.bfloat16)             # [hn, group]
        lt = (lo_iota == lo).astype(jnp.bfloat16)             # [128, group]
        return acc + jax.lax.dot_general(
            ht, lt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [hn, 128]

    acc = jax.lax.fori_loop(0, block_rows // GROUP_ROWS, body,
                            jnp.zeros((hn, LANES), jnp.float32))
    o_ref[...] += acc.astype(jnp.int32)


def bucket_counts_pallas(ids: jax.Array, Q: int, *,
                         interpret: bool = True) -> jax.Array:
    """ids: [B, n_rows, 128] int32 keys in [0, Q), -1 for none
    -> [B, Hn, 128] int32 counts of key 128*h + l at [b, h, l]."""
    B, n_rows, lanes = ids.shape
    assert lanes == LANES, ids.shape
    hn = -(-max(Q, 1) // (8 * LANES)) * 8      # ceil(Q/128) rounded up to 8
    block_rows = min(BLOCK_ROWS, -(-n_rows // GROUP_ROWS) * GROUP_ROWS)
    return pl.pallas_call(
        functools.partial(_count_kernel, hn=hn, block_rows=block_rows,
                          n_rows=n_rows),
        # varying over the mesh axes the ids vary over, inside a shard_map
        out_shape=jax.ShapeDtypeStruct((B, hn, LANES), jnp.int32,
                                       vma=jax.typeof(ids).vma),
        grid=(B, pl.cdiv(n_rows, block_rows)),
        in_specs=[pl.BlockSpec((None, block_rows, LANES),
                               lambda b, t: (b, t, 0))],
        out_specs=pl.BlockSpec((None, hn, LANES), lambda b, t: (b, 0, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="bucket_count",
    )(ids)
