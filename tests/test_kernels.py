"""Per-kernel validation: shape/dtype sweeps, allclose vs the ref.py
pure-jnp oracles (Pallas executed in interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bucket_count import (kernel as bc_kernel, ops as bc_ops,
                                       ref as bc_ref)
from repro.kernels.coded_combine import ops as cc_ops, ref as cc_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.rwkv_scan import ops as rw_ops, ref as rw_ref

KEY = jax.random.PRNGKey(0)


def _k(i):
    return jax.random.fold_in(KEY, i)


# ---------------------------------------------------------------------------
# coded_combine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r", [2, 3, 4])
@pytest.mark.parametrize("T,d", [(64, 128), (100, 96), (257, 40),
                                 (1, 7), (300, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coded_encode_decode(r, T, d, dtype):
    streams = [jax.random.normal(_k(i), (T, d), jnp.float32).astype(dtype)
               for i in range(r)]
    coeffs = jnp.arange(1.0, r + 1.0)
    f = cc_ops.coded_encode(streams, coeffs)
    ref = cc_ref.encode_ref(jnp.stack(streams), coeffs)
    tol = 1e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(f, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    # decode stream 0 from f + streams[1:]
    dec = cc_ops.coded_decode(f, streams[1:], coeffs)
    # bf16 round-trip: decode subtracts large partial sums, so near-zero
    # elements see catastrophic cancellation — absolute tolerance scaled
    # to the bf16 ulp of the SUM magnitude, not the value
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(streams[0], np.float32),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=0.15 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("r", [2, 3])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.uint32])
def test_xor_roundtrip(r, dtype):
    T, d = 80, 64
    streams = [jax.random.randint(_k(10 + i), (T, d), 0, 2 ** 30
                                  ).astype(dtype) for i in range(r)]
    f = cc_ops.xor_encode(streams)
    np.testing.assert_array_equal(
        np.asarray(f), np.asarray(cc_ref.xor_encode_ref(jnp.stack(streams))))
    dec = cc_ops.xor_decode(f, streams[1:])
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(streams[0]))


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

def _ref_model_layout(q, k, v, **kw):
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(B * KV, G, Sq, hd)
    kg = k.transpose(0, 2, 1, 3).reshape(B * KV, -1, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(B * KV, -1, hd)
    o = fa_ref.flash_attention_ref(qg, kg, vg, **kw)
    return o.reshape(B, KV, G, Sq, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(B, Sq, H, hd)


@pytest.mark.parametrize("B,Sq,Sk,H,KV,hd", [
    (2, 128, 128, 4, 4, 64),      # MHA
    (1, 200, 200, 8, 2, 64),      # GQA, ragged seq
    (2, 64, 256, 4, 1, 128),      # MQA, cross-length (decode-ish)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_vs_ref(B, Sq, Sk, H, KV, hd, dtype, causal):
    q = jax.random.normal(_k(1), (B, Sq, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(_k(2), (B, Sk, KV, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(_k(3), (B, Sk, KV, hd), jnp.float32).astype(dtype)
    q_off = Sk - Sq if causal else 0
    out = fa_ops.flash_attention(q, k, v, causal=causal, q_offset=q_off,
                                 block_q=64, block_k=64)
    ref = _ref_model_layout(q, k, v, causal=causal, q_offset=q_off)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_window():
    B, S, H, KV, hd = 1, 160, 4, 2, 64
    q = jax.random.normal(_k(4), (B, S, H, hd))
    k = jax.random.normal(_k(5), (B, S, KV, hd))
    v = jax.random.normal(_k(6), (B, S, KV, hd))
    out = fa_ops.flash_attention(q, k, v, causal=True, window=32,
                                 block_q=32, block_k=32)
    ref = _ref_model_layout(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_kv_valid():
    """Decode-style masking: only the first kv_valid keys count."""
    B, Sq, Sk, H, KV, hd = 2, 8, 128, 4, 4, 64
    q = jax.random.normal(_k(7), (B, Sq, H, hd))
    k = jax.random.normal(_k(8), (B, Sk, KV, hd))
    v = jax.random.normal(_k(9), (B, Sk, KV, hd))
    out = fa_ops.flash_attention(q, k, v, causal=False, kv_valid=57,
                                 block_q=8, block_k=32)
    ref = _ref_model_layout(q, k, v, causal=False, kv_valid=57)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_matches_model_attention():
    """The kernel is the oracle-equal fast path of models.attention."""
    from repro.models.attention import dense_attention
    B, S, H, KV, hd = 2, 96, 8, 2, 64
    q = jax.random.normal(_k(11), (B, S, H, hd))
    k = jax.random.normal(_k(12), (B, S, KV, hd))
    v = jax.random.normal(_k(13), (B, S, KV, hd))
    out = fa_ops.flash_attention(q, k, v, causal=True, block_q=32,
                                 block_k=32)
    ref = dense_attention(q, k, v, jnp.arange(S), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# rwkv_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,h,Nk,Nv,chunk", [
    (1, 64, 2, 16, 16, 16),
    (2, 100, 3, 32, 32, 32),      # ragged: S % chunk != 0
    (1, 128, 1, 64, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv_scan_vs_ref(B, S, h, Nk, Nv, chunk, dtype):
    r = jax.random.normal(_k(20), (B, S, h, Nk), jnp.float32).astype(dtype)
    k = jax.random.normal(_k(21), (B, S, h, Nk), jnp.float32).astype(dtype)
    v = jax.random.normal(_k(22), (B, S, h, Nv), jnp.float32).astype(dtype)
    w = -jnp.exp(jax.random.normal(_k(23), (B, S, h, Nk)))
    u = 0.1 * jax.random.normal(_k(24), (h, Nk))
    s0 = jax.random.normal(_k(25), (B, h, Nk, Nv)) * 0.1
    out, sT = rw_ops.wkv_scan(r, k, v, w.astype(dtype), u, s0, chunk=chunk)
    from repro.models.linrec import chunked_linear_recurrence
    oref, sref = chunked_linear_recurrence(
        r, k, v, w.astype(dtype), u=u, initial_state=s0, mode="rwkv",
        chunk=chunk, return_state=True)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oref, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sref),
                               rtol=tol, atol=tol)


def test_wkv_scan_vs_naive_steps():
    """Kernel == step-by-step recurrence (the ground-truth semantics)."""
    from repro.models.linrec import naive_linear_recurrence
    B, S, h, N = 1, 48, 2, 16
    r = jax.random.normal(_k(30), (B, S, h, N))
    k = jax.random.normal(_k(31), (B, S, h, N))
    v = jax.random.normal(_k(32), (B, S, h, N))
    w = -jnp.exp(jax.random.normal(_k(33), (B, S, h, N)))
    u = 0.1 * jax.random.normal(_k(34), (h, N))
    out, sT = rw_ops.wkv_scan(r, k, v, w, u, chunk=16)
    oref, sref = naive_linear_recurrence(r, k, v, w, u=u, mode="rwkv")
    np.testing.assert_allclose(np.asarray(out), np.asarray(oref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sref),
                               rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# bucket_count
# ---------------------------------------------------------------------------

# three id blocks, the last one partial and T not a multiple of 128
BC_T = 2 * bc_kernel.BLOCK_ROWS * bc_kernel.LANES + 333


def _bucket_keys(mix, Q, shape, seed):
    rng = np.random.default_rng(seed)
    if mix == "uniform":
        keys = rng.integers(0, Q, shape)
    elif mix == "one_key":          # every id of a row in one key
        keys = np.full(shape, Q - 1)
    else:
        keys = (rng.zipf(1.3, shape) - 1) % Q
    return keys.astype(np.int32)


@pytest.mark.parametrize("Q", [1, 7, 128, 1000, 1024, 4097])
@pytest.mark.parametrize("mix", ["uniform", "one_key", "zipf"])
def test_bucket_counts_vs_bincount(Q, mix):
    """Exact int32 counts, vmapped over a batch of rows, whatever the skew:
    one key holding all of a row's ids sums its per-block f32 partials in
    int32 across the three blocks."""
    keys = _bucket_keys(mix, Q, (2, BC_T), seed=Q)
    got = jax.vmap(lambda b: bc_ops.bucket_counts_mxu(b, Q))(
        jnp.asarray(keys))
    assert got.dtype == jnp.int32
    want = np.stack([np.bincount(row, minlength=Q) for row in keys])
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("shape", [(1,), (5,), (2, 3, 130), (4, 1, 1024)])
def test_bucket_counts_leading_dims_and_short_rows(shape):
    keys = _bucket_keys("uniform", 300, shape, seed=len(shape))
    got = np.asarray(bc_ops.bucket_counts_mxu(jnp.asarray(keys), 300))
    want = np.asarray(bc_ref.scatter_counts(jnp.asarray(keys), 300))
    assert got.shape == shape[:-1] + (300,)
    np.testing.assert_array_equal(got, want)


def test_bucket_counts_path_and_program_counter(monkeypatch):
    """Off TPU the helper keeps the scatter; on TPU (steered here) it takes
    the kernel up to MAX_Q.  The counter counts traces, not calls."""
    from repro.obs import metrics
    metrics.reset()
    programs = metrics.counter("bucket_count_programs_total")
    x = jnp.arange(3000, dtype=jnp.int32) % 7
    f = jax.jit(lambda b: bc_ops.bucket_counts(b, 7, jnp.float32))
    for _ in range(2):
        out = f(x)
        assert out.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(out),
                                      np.bincount(np.asarray(x)))
    assert (programs.value(impl="scatter"), programs.value(impl="mxu")) \
        == (1, 0)

    monkeypatch.setattr(bc_ops, "_on_tpu", lambda: True)
    jaxpr = str(jax.make_jaxpr(lambda b: bc_ops.bucket_counts(b, 7))(x))
    assert "pallas_call" in jaxpr and "scatter" not in jaxpr
    big = bc_kernel.MAX_Q + 1                   # past the VMEM limit
    jaxpr = str(jax.make_jaxpr(lambda b: bc_ops.bucket_counts(b, big))(x))
    assert "pallas_call" not in jaxpr and "scatter-add" in jaxpr
    assert (programs.value(impl="scatter"), programs.value(impl="mxu")) \
        == (2, 1)
