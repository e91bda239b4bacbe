"""Pallas TPU kernel for the paper's linear combining function f(.).

The Hybrid Coded MapReduce multicast payload is  f(v_1, ..., v_r) =
sum_i c_i * v_i  (eq. (1) of the paper); a receiver holding all but one
stream decodes the missing value as  (f - sum_known c_i v_i) / c_miss.
On TPU this encode/decode is a *memory-bound* fused multiply-accumulate
over the payload tensors — the hot inner loop of the shuffle engine, fused
here so each tile is read from HBM exactly once into VMEM.

Tiling: payloads are flattened to [T, d]; the stream axis r is small
(the map replication factor, 2-4) and unrolled inside the kernel.  The grid
is (T / block_t, d / block_d) with d padded to the 128-lane boundary by
ops.py.  block_d is capped (:func:`lane_block`) so the VMEM working set —
r input tiles plus the output tile, each double-buffered — stays inside the
default scoped VMEM limit at any shuffle width: a full-d block at
(r=3, d=2048) or (r=2, d=4096) does not fit.

An XOR (GF(2)) variant is provided for bit-exact integer shuffles
(CodedTeraSort-style): f = v_1 ^ ... ^ v_r, decode by re-XOR.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _encode_kernel(x_ref, c_ref, o_ref, *, r: int):
    """x: [r, bt, d]; c: [r] fp32; o: [bt, d] = sum_i c[i] * x[i]."""
    acc = c_ref[0] * x_ref[0].astype(jnp.float32)
    for i in range(1, r):
        acc += c_ref[i] * x_ref[i].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def _decode_kernel(f_ref, x_ref, c_ref, o_ref, *, r: int):
    """f: [bt, d]; x (known): [r-1, bt, d]; c: [r] with c[0] = coefficient of
    the MISSING stream; c[1:] of the known ones.  o = (f - sum c_i x_i)/c[0].
    """
    acc = f_ref[...].astype(jnp.float32)
    for i in range(r - 1):
        acc -= c_ref[i + 1] * x_ref[i].astype(jnp.float32)
    o_ref[...] = (acc / c_ref[0]).astype(o_ref.dtype)


def _xor_encode_kernel(x_ref, o_ref, *, r: int):
    acc = x_ref[0]
    for i in range(1, r):
        acc = acc ^ x_ref[i]
    o_ref[...] = acc


def _xor_decode_kernel(f_ref, x_ref, o_ref, *, r: int):
    acc = f_ref[...]
    for i in range(r - 1):
        acc = acc ^ x_ref[i]
    o_ref[...] = acc


_MAX_BLOCK_D = 512


def lane_block(d: int) -> int:
    """Largest multiple of 128 that divides ``d`` and is <= _MAX_BLOCK_D.
    ``d`` is 128-aligned (ops.py pads it).  At block_t=256, r=4, f32 that
    is 4 MiB of double-buffered input tiles."""
    assert d % 128 == 0, d
    return max(b for b in range(128, min(d, _MAX_BLOCK_D) + 1, 128)
               if d % b == 0)


def encode_pallas(streams: jax.Array, coeffs: jax.Array, *,
                  block_t: int = 256, interpret: bool = True) -> jax.Array:
    """streams: [r, T, d]; coeffs: [r] fp32 -> f [T, d] (streams dtype)."""
    r, T, d = streams.shape
    bd = lane_block(d)
    return pl.pallas_call(
        functools.partial(_encode_kernel, r=r),
        out_shape=jax.ShapeDtypeStruct((T, d), streams.dtype),
        grid=(T // block_t, d // bd),
        in_specs=[pl.BlockSpec((r, block_t, bd), lambda i, k: (0, i, k)),
                  pl.BlockSpec((r,), lambda i, k: (0,))],
        out_specs=pl.BlockSpec((block_t, bd), lambda i, k: (i, k)),
        interpret=interpret,
    )(streams, coeffs.astype(jnp.float32))


def decode_pallas(f: jax.Array, known: jax.Array, coeffs: jax.Array, *,
                  block_t: int = 256, interpret: bool = True) -> jax.Array:
    """f: [T, d]; known: [r-1, T, d]; coeffs: [r] (missing first)."""
    rm1, T, d = known.shape
    bd = lane_block(d)
    return pl.pallas_call(
        functools.partial(_decode_kernel, r=rm1 + 1),
        out_shape=jax.ShapeDtypeStruct((T, d), f.dtype),
        grid=(T // block_t, d // bd),
        in_specs=[pl.BlockSpec((block_t, bd), lambda i, k: (i, k)),
                  pl.BlockSpec((rm1, block_t, bd), lambda i, k: (0, i, k)),
                  pl.BlockSpec((rm1 + 1,), lambda i, k: (0,))],
        out_specs=pl.BlockSpec((block_t, bd), lambda i, k: (i, k)),
        interpret=interpret,
    )(f, known, coeffs.astype(jnp.float32))


def xor_encode_pallas(streams: jax.Array, *, block_t: int = 256,
                      interpret: bool = True) -> jax.Array:
    r, T, d = streams.shape
    bd = lane_block(d)
    return pl.pallas_call(
        functools.partial(_xor_encode_kernel, r=r),
        out_shape=jax.ShapeDtypeStruct((T, d), streams.dtype),
        grid=(T // block_t, d // bd),
        in_specs=[pl.BlockSpec((r, block_t, bd), lambda i, k: (0, i, k))],
        out_specs=pl.BlockSpec((block_t, bd), lambda i, k: (i, k)),
        interpret=interpret,
    )(streams)


def xor_decode_pallas(f: jax.Array, known: jax.Array, *, block_t: int = 256,
                      interpret: bool = True) -> jax.Array:
    rm1, T, d = known.shape
    bd = lane_block(d)
    return pl.pallas_call(
        functools.partial(_xor_decode_kernel, r=rm1 + 1),
        out_shape=jax.ShapeDtypeStruct((T, d), f.dtype),
        grid=(T // block_t, d // bd),
        in_specs=[pl.BlockSpec((block_t, bd), lambda i, k: (i, k)),
                  pl.BlockSpec((rm1, block_t, bd), lambda i, k: (0, i, k))],
        out_specs=pl.BlockSpec((block_t, bd), lambda i, k: (i, k)),
        interpret=interpret,
    )(f, known)
