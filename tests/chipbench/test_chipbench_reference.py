"""The benchmark's plain NumPy references against the engine's own
single-device ``run_job`` on the CPU at a tiny size, per-job input
transform included, and the control (the reference in bfloat16), which
the comparison must fail."""
import dataclasses

import numpy as np
import pytest

from chipbench.harness import base_tokens, compare, load_cell

CELLS = ["wordcount.1chip"]


def _tiny(cell_name, **sizes):
    cell = load_cell(cell_name)
    return dataclasses.replace(cell, config=dict(cell.config, **sizes))


@pytest.mark.parametrize("cell_name", CELLS)
def test_reference_matches_run_job_with_job_transform(cell_name):
    import jax.numpy as jnp
    from repro.core.params import SchemeParams
    from repro.mapreduce import jobs
    from repro.mapreduce.engine import run_job

    cell = _tiny(cell_name, N=3, tokens_per_subfile=2048)
    cfg, ref = cell.config, cell.reference
    job = getattr(jobs, cfg["job"])(**cfg["job_args"])
    params = SchemeParams(K=1, P=1, Q=cfg["Q"], N=cfg["N"], r=1)
    base = base_tokens(cfg, 2**31 + 5)
    state = ref.prepare(base, cfg)
    for k in (0, 1, 7, 999, 1000, 1025):
        x = ref.job_input(base, k, cfg)
        assert x.dtype == np.int32 and x.shape == base.shape
        got = np.asarray(run_job(job, jnp.asarray(x), params).outputs)
        want = ref.expected(state, k, cfg)
        np.testing.assert_array_equal(want, ref.direct(x, cfg))
        assert compare([got], [want]) == {"max_abs_err": 0.0,
                                          "jobs_wrong": 0}


@pytest.mark.parametrize("cell_name", CELLS)
def test_job_inputs_differ_from_job_to_job(cell_name):
    cell = _tiny(cell_name, N=2, tokens_per_subfile=512)
    cfg, ref = cell.config, cell.reference
    base = base_tokens(cfg, 11)
    ks = [0, 1, 2, 5, 1000, 1001]         # wordcount wraps at V = 1000
    xs = [ref.job_input(base, k, cfg).tobytes() for k in ks]
    assert len(set(xs)) == len(xs)


def test_seed_fixes_the_inputs():
    cfg = load_cell("wordcount.1chip").config
    cfg = dict(cfg, N=2, tokens_per_subfile=64)
    a, b = base_tokens(cfg, 2**31 + 9), base_tokens(cfg, 2**31 + 9)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, base_tokens(cfg, 2**31 + 10))
    assert a.min() >= 0


@pytest.mark.parametrize("cell_name", CELLS)
def test_bf16_control_fails_the_comparison(cell_name):
    # 2^18 tokens over ~1000 keys: counts above 256, which bfloat16's
    # 8-bit significand cannot hold exactly
    cell = _tiny(cell_name, N=3, tokens_per_subfile=1 << 18)
    cfg, ref = cell.config, cell.reference
    base = base_tokens(cfg, 3)
    state = ref.prepare(base, cfg)
    got = [ref.control(base, k, cfg) for k in (1, 2)]
    want = [ref.expected(state, k, cfg) for k in (1, 2)]
    checks = compare(got, want)
    assert checks["max_abs_err"] > 0 and checks["jobs_wrong"] == 2


def test_compare_reads_missing_jobs_shapes_and_nans_as_failures():
    w = np.ones((4, 2), np.float32)
    assert compare([w], [w, w])["max_abs_err"] == float("inf")
    assert compare([w[:2]], [w])["jobs_wrong"] == 1
    assert compare([w.astype(np.float64)], [w])["jobs_wrong"] == 1
    bad = w.copy()
    bad[0, 0] = np.nan
    assert compare([bad], [w])["max_abs_err"] == float("inf")
