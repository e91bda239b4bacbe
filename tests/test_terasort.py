"""``terasort_job`` against its plain NumPy reference
(``repro.mapreduce.terasort_ref``) on the CPU at a tiny size: through
``run_job`` on one device and through ``run_job_distributed`` on four
virtual devices under every shuffle the cell's mesh admits; a planted
overflow; tied keys; the odd-even fix-up from any order; and the record
slot counter."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.params import SchemeParams
from repro.mapreduce import jobs
from repro.mapreduce.engine import run_job, run_job_distributed
from repro.mapreduce.terasort_ref import terasort_summary
from repro.obs import metrics

ROOT = pathlib.Path(__file__).resolve().parent.parent
N, RECORDS, Q, CAPACITY = 12, 256, 8, 80


def _records(seed, n_keys=None):
    """[N, RECORDS * 25] int32 TeraGen-like records: uniform keys (or
    ``n_keys`` distinct ones), zero break bytes, shuffled unique row ids."""
    rng = np.random.default_rng(seed)
    rec = rng.integers(0, 2**32, size=(N * RECORDS, 25),
                       dtype=np.uint64).astype(np.uint32)
    if n_keys is not None:
        keys = rec[rng.integers(0, n_keys, N * RECORDS), :3]
        rec[:, :3] = keys
    rec[:, 2] &= 0xFFFF0000
    rec[:, 3] = rng.permutation(N * RECORDS)
    return rec.view(np.int32).reshape(N, RECORDS * 25)


def _one_device(x, capacity=CAPACITY):
    job = jobs.terasort_job(RECORDS, Q, capacity)
    p = SchemeParams(K=1, P=1, Q=Q, N=N, r=1)
    return np.asarray(run_job(job, jnp.asarray(x), p).outputs)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_run_job_matches_the_reference(seed):
    x = _records(seed)
    got = _one_device(x)
    np.testing.assert_array_equal(got, terasort_summary(x, Q, CAPACITY))
    assert got[:, 0].sum() == N * RECORDS and not got[:, 1].any()
    assert not got[:, 8].any()                   # no pair out of order


_FOUR_DEVICES = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
sys.path.insert(0, sys.argv[1])
from test_terasort import _records, N, RECORDS, Q, CAPACITY
from repro.core.params import SchemeParams
from repro.mapreduce.engine import run_job_distributed
from repro.mapreduce.jobs import terasort_job
from repro.mapreduce.terasort_ref import terasort_summary
x = _records(3)
want = terasort_summary(x, Q, CAPACITY)
job = terasort_job(RECORDS, Q, CAPACITY)
out = {}
for mesh, r, multicast, impl in [((4, 1), 2, "coded_xor", "xla"),
                                 ((4, 1), 2, "coded_xor", "pallas"),
                                 ((4, 1), 2, "unicast", "xla"),
                                 ((2, 2), 1, "unicast", "xla")]:
    res = run_job_distributed(
        job, x, SchemeParams(K=4, P=mesh[0], Q=Q, N=N, r=r),
        jax.make_mesh(mesh, ("rack", "server")), multicast=multicast,
        combine_impl=impl)
    out[f"{mesh} r={r} {multicast}/{impl}"] = bool(
        (np.asarray(res.outputs) == want).all())
print(json.dumps(out))
"""


def test_four_devices_match_the_reference():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES, str(ROOT / "tests")],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(out) == 4 and all(out.values()), out


def test_planted_overflow_reads_wrong():
    """A capacity below a block's count drops records: the count and the
    checksum say so, and the overflow names how many."""
    x = _records(1)
    blocks = RECORDS // Q
    got = _one_device(x, capacity=blocks - 8)
    want = terasort_summary(x, Q, CAPACITY)
    assert got[:, 1].sum() > 0
    assert got[:, 0].sum() + got[:, 1].sum() == N * RECORDS
    assert (got[:, 0] < want[:, 0]).any() and (got[:, 9] != want[:, 9]).any()


def test_tied_keys_are_ordered_by_row_id():
    x = _records(2, n_keys=5)
    # a block may hold a whole subfile: five keys over eight partitions
    got = _one_device(x, capacity=RECORDS)
    np.testing.assert_array_equal(got, terasort_summary(x, Q, RECORDS))
    # the checksum sees the order of the ties: by key alone, any order of
    # the row ids within a key would do, and most give another checksum
    rec = x.view(np.uint32).reshape(-1, 25).copy()
    rec[:, 3] = rec[::-1, 3]
    flipped = terasort_summary(rec.view(np.int32).reshape(x.shape), Q,
                               RECORDS)
    assert (flipped[:, 9] != got[:, 9]).any()


@pytest.mark.parametrize("seed", range(3))
def test_transposition_sort_is_exact_from_any_order(seed):
    rng = np.random.default_rng(seed)
    cols = [rng.integers(0, 3, 300).astype(np.uint32) for _ in range(3)]
    idx = rng.permutation(300).astype(np.int32)
    got = np.asarray(jobs._transposition_sort(
        [jnp.asarray(c) for c in cols], jnp.asarray(idx)))
    want = idx[np.lexsort(cols[::-1])]
    keys = np.stack(cols, axis=1)
    # ties among equal keys may land in any order: compare the keys
    pos = {int(v): i for i, v in enumerate(idx)}
    np.testing.assert_array_equal(keys[[pos[int(v)] for v in got]],
                                  keys[[pos[int(v)] for v in want]])
    assert sorted(got.tolist()) == sorted(idx.tolist())


def test_record_slot_counter_counts_records_and_padding():
    job = jobs.terasort_job(RECORDS, Q, CAPACITY)
    counter = metrics.counter("shuffle_record_slots_total")
    before = {k: counter.value(job=job.name, kind=k)
              for k in ("record", "padding")}
    run_job_distributed(job, _records(4), SchemeParams(K=1, P=1, Q=Q, N=N,
                                                       r=1),
                        jax.make_mesh((1, 1), ("rack", "server"),
                                      devices=jax.devices()[:1]))
    assert counter.value(job=job.name, kind="record") - before["record"] \
        == N * RECORDS
    assert counter.value(job=job.name, kind="padding") - before["padding"] \
        == N * Q * CAPACITY - N * RECORDS


def test_terasort_job_refuses_q_not_a_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        jobs.terasort_job(RECORDS, 12, CAPACITY)
