"""Runs of the benchmark without a chip: the command refuses the CPU, and
the rest of a run, driven on the CPU at a tiny size, says ``correct``
for the engine as it is and not ``correct`` for each fault planted in the
timed path."""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

from chipbench.harness import load_cell, run_cell

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TINY = {"wordcount.1chip": dict(N=4, tokens_per_subfile=4096)}


def _run(cell_name: str) -> dict:
    cell = load_cell(cell_name)
    cell = dataclasses.replace(cell,
                               config=dict(cell.config, **TINY[cell_name]))
    return run_cell(cell, 2**31 + 1, 0.2, False, time.perf_counter(),
                    log=lambda *_: None)


def test_command_exits_without_a_result_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "wordcount.1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout
    assert "TPU" in proc.stderr


@pytest.mark.parametrize("cell_name", sorted(TINY))
def test_sound_run_is_correct(cell_name):
    res = _run(cell_name)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"input_gib_per_s", "jct_p50_s",
                                   "jct_p90_s", "setup_s"}


def _stale(real):
    """Every job does its work but hands back the first job's result:
    state left unchanged."""
    first = []

    def fn(*a, **k):
        first.append(real(*a, **k))
        return first[0]
    return fn


def _half_batch(real):
    """The second half of the subfiles replaced by the first: half the
    batch left out, the rest counted twice (the mean over the rest)."""
    def fn(job, subfiles, *a, **k):
        x = subfiles.copy()
        h = len(x) // 2
        x[h:2 * h] = x[:h]
        return real(job, x, *a, **k)
    return fn


def _altered_answer(real):
    """One output value off by one where the engine produces it."""
    def fn(*a, **k):
        res = real(*a, **k)
        return dataclasses.replace(res, outputs=res.outputs.at[0, 0].add(1))
    return fn


@pytest.mark.parametrize("cell_name", sorted(TINY))
@pytest.mark.parametrize("fault", [_stale, _half_batch, _altered_answer])
def test_planted_fault_is_not_correct(cell_name, fault, monkeypatch):
    from repro.mapreduce import engine
    monkeypatch.setattr(engine, "run_job_distributed",
                        fault(engine.run_job_distributed))
    res = _run(cell_name)
    assert not res["correct"]
    assert res["failed"] > 0
    assert res["compared"]["max_abs_err"]["value"] > \
        res["compared"]["max_abs_err"]["limit"]


@pytest.mark.parametrize("mode,correct", [("sound", True),
                                          ("no_exchange", False)])
def test_four_chip_traffic_without_the_exchange_is_not_correct(mode,
                                                               correct):
    proc = subprocess.run(
        [sys.executable, str(HERE / "four_chip_driver.py"), mode],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=str(ROOT / "src")))
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is correct
    assert res["attempted"] > 0 and (res["failed"] > 0) is not correct
