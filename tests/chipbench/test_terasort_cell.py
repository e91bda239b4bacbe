"""The ``terasort.coded.4chip`` cell without a chip: its plain NumPy
reference (``expected`` read off one sorted base against ``direct`` from
scratch, and the unsorted control, which must fail), a tiny run of the
cell on four virtual CPU devices (``correct`` as the engine is, not
``correct`` without the exchange or without the reducer's sort), its three
per-layer readers, and the engine's stage table: one compile per program,
and the word-count program's table unchanged by the ``reducer_sort``
stage."""
import dataclasses
import json
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench import trace
from chipbench.harness import base_tokens, compare, load_cell, load_module
from repro.mapreduce import engine
from repro.obs.tracing import op_stages

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
METRICS = ROOT / "chipbench" / "metrics"
CELL = "terasort.coded.4chip"


def _cell(N, records, Q, capacity):
    cell = load_cell(CELL)
    return dataclasses.replace(cell, config=dict(
        cell.config, N=N, Q=Q, tokens_per_subfile=records * 25,
        job_args=dict(records_per_subfile=records, Q=Q, capacity=capacity)))


@pytest.mark.parametrize("sizes", [(6, 1000, 4, 300), (12, 256, 8, 30),
                                   (3, 50, 2, 10)],
                         ids=["uniform", "overflowing", "tiny"])
def test_expected_is_direct_on_every_job(sizes):
    cell = _cell(*sizes)
    cfg, ref = cell.config, cell.reference
    base = base_tokens(cfg, 2**31 + 7)
    state = ref.prepare(base, cfg)
    for k in (0, 1, 2, 5, 1000, 2**31 + 3, 2**33 + 1):
        x = ref.job_input(base, k, cfg)
        assert x.dtype == np.int32 and x.shape == base.shape
        np.testing.assert_array_equal(ref.expected(state, k, cfg),
                                      ref.direct(x, cfg))


def test_job_inputs_are_teragen_records_new_every_job():
    cell = _cell(6, 1000, 4, 300)
    cfg, ref = cell.config, cell.reference
    base = base_tokens(cfg, 5)
    xs = [ref.job_input(base, k, cfg) for k in (0, 1, 2)]
    assert len({x.tobytes() for x in xs}) == 3
    rec = xs[1].view(np.uint32).reshape(-1, 25)
    assert not (rec[:, 2] & 0xFFFF).any()            # zero break bytes
    np.testing.assert_array_equal(rec[:, 3], np.arange(len(rec)))
    assert (rec[:, 0] >> 31).any() and (rec[:, 1] >> 31).any()
    np.testing.assert_array_equal(xs[1].view(np.uint32).reshape(-1, 25)[
        :, 4:], base.view(np.uint32).reshape(-1, 25)[:, 4:])


def test_unsorted_control_fails_the_comparison():
    cell = _cell(6, 1000, 4, 300)
    cfg, ref = cell.config, cell.reference
    base = base_tokens(cfg, 3)
    state = ref.prepare(base, cfg)
    got = [ref.control(base, k, cfg) for k in (1, 2)]
    checks = compare(got, [ref.expected(state, k, cfg) for k in (1, 2)])
    assert checks["max_abs_err"] > 0 and checks["jobs_wrong"] == 2
    # a partition in map order holds the right records, out of order
    np.testing.assert_array_equal(got[0][:, 0],
                                  ref.expected(state, 1, cfg)[:, 0])
    assert (got[0][:, 8] > 0).all()


def test_tiny_four_chip_run_is_correct_only_with_exchange_and_sort():
    proc = subprocess.run(
        [sys.executable, str(HERE / "terasort_four_chip_driver.py"),
         "sound", "no_exchange", "no_sort"],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    runs = {r["mode"]: r for r in map(json.loads,
                                      proc.stdout.strip().splitlines())}
    assert runs["sound"]["correct"] and runs["sound"]["failed"] == 0
    for mode in ("no_exchange", "no_sort"):
        assert not runs[mode]["correct"]
        assert runs[mode]["failed"] == runs[mode]["attempted"] > 0


def test_new_readers_per_job(monkeypatch):
    ops = {"%a2a = s32[4] all-to-all(": [0.4e9, 4],
           "%enc = s32[4] custom-call(": [0.2e9, 4],
           "%dec = s32[4] custom-call(": [0.1e9, 4],
           "%gather = s32[4] fusion(": [0.05e9, 4],
           "%mixed = s32[4] fusion(": [0.3e9, 4],
           "%sort = u32[4] sort(": [0.6e9, 4]}
    s = trace.Summary(8e9, [2e9, 2e9], [ops, dict(ops)], {})
    monkeypatch.setattr(engine, "fused_op_stages", lambda: {
        "a2a = s32[4] all-to-all": "stage1",
        "enc = s32[4] custom-call": "encode",
        "dec = s32[4] custom-call": "decode",
        "gather = s32[4] fusion": "stage1+encode",
        "mixed = s32[4] fusion": "map+encode",
        "sort = u32[4] sort": "reducer_sort"})
    w = types.SimpleNamespace(trace=s, device_kind="TPU v5 lite", jobs=4,
                              spans={}, cell=load_cell(CELL))
    read = {n: load_module(str(METRICS / f"{n}.py"), f"r_tera_{n}").read(w)
            for n in ("stage1_device_ms", "reducer_sort_device_ms",
                      "codec_hbm_roofline_pct")}
    # per chip and job: stage 1 0.1 + 0.05 + 0.025 + 0.0125 s (the joint
    # map+encode op counts under neither); the sort 0.15 s; the codec's
    # 360185856 bytes in 0.075 s of encode and decode
    assert read == pytest.approx({
        "stage1_device_ms": 187.5, "reducer_sort_device_ms": 150.0,
        "codec_hbm_roofline_pct": 100 * 360185856 / 0.075 / 819e9})


def test_codec_bytes_of_the_cell():
    reader = load_module(str(METRICS / "codec_hbm_roofline_pct.py"),
                         "r_codec_bytes")
    # 2 (r + 1) T d 4 with T = 128 packet rows and d = 117248 words
    assert reader.codec_bytes(load_cell(CELL)) == 2 * 3 * 128 * 117248 * 4


def test_fused_op_stages_compiles_each_program_once(monkeypatch):
    import jax
    from chipbench.harness import CompileCounter
    from repro.core.params import SchemeParams
    from repro.mapreduce.jobs import histogram_job
    monkeypatch.setattr(engine, "_FUSED_CALLS", {})
    x = np.random.default_rng(0).integers(0, 16, (4, 64)).astype(np.int32)
    engine.run_job_distributed(
        histogram_job(), x, SchemeParams(K=1, P=1, Q=16, N=4, r=1),
        jax.make_mesh((1, 1), ("rack", "server"), devices=jax.devices()[:1]))
    first = engine.fused_op_stages()
    compiles = CompileCounter()
    assert engine.fused_op_stages() == first and first
    assert compiles.count == 0
    # the word-count program's table, as it was before reducer_sort
    text, = engine._FUSED_TEXTS.values()
    old = tuple(s for s in engine.FUSED_STAGES if s != "reducer_sort")
    assert first == op_stages(text, old)


def test_wordcount_stage_table_is_unchanged_by_the_new_stage():
    old = tuple(s for s in engine.FUSED_STAGES if s != "reducer_sort")
    assert "reducer_sort" in engine.FUSED_STAGES
    text = (HERE / "wordcount_1chip_fused.hlo.txt").read_text()
    assert op_stages(text, engine.FUSED_STAGES) == op_stages(text, old)
