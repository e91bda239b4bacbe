"""Drive runs of the ``terasort.coded.4chip`` cell on four virtual CPU
devices at a tiny size (12 subfiles of 256 records, Q = 8), as the cell's
own traffic runs them (mesh 4 racks x 1 server, r = 2, XOR-coded stage 1
on the Pallas codec in interpret mode), and print one result line per
mode: ``sound``; ``no_exchange``, every all_to_all returning the sender's
own blocks; ``no_sort``, the reducer keeping its records in map order.
Started by test_terasort_cell.py in a child process, since JAX fixes its
device count when it starts.

    python terasort_four_chip_driver.py sound no_exchange no_sort
"""
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import dataclasses  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench.harness import load_cell, run_cell  # noqa: E402
from repro.mapreduce import jobs  # noqa: E402

TINY = dict(N=12, Q=8, tokens_per_subfile=256 * 25,
            job_args=dict(records_per_subfile=256, Q=8, capacity=80))


def tiny_cell():
    cell = load_cell("terasort.coded.4chip")
    return dataclasses.replace(cell, config=dict(cell.config, **TINY))


def main(modes) -> None:
    all_to_all, record_order = jax.lax.all_to_all, jobs._record_order
    for mode in modes:
        jax.lax.all_to_all, jobs._record_order = all_to_all, record_order
        if mode == "no_exchange":
            jax.lax.all_to_all = lambda x, *a, **k: x
        elif mode == "no_sort":
            jobs._record_order = lambda words, live, s: jnp.arange(
                live.shape[0])
        result = run_cell(tiny_cell(), 2**33 + 17, 0.2, False,
                          time.perf_counter(), log=lambda *_: None)
        print(json.dumps({"mode": mode, **result}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
