"""Drive a run of the word-count cell on four virtual CPU devices at a tiny
size, under a four-chip traffic mix (mesh 4 racks x 1 server, r = 2, the
coded multicast), optionally with the exchange between chips left out, and
print the result line.  Started by test_chipbench_run.py in a child
process, since JAX fixes its device count when it starts.

    python four_chip_driver.py sound|no_exchange
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

from chipbench.harness import load_cell, run_cell  # noqa: E402

FOUR_CHIPS = {"what": "4 racks x 1 server, r = 2, coded multicast",
              "mesh": [4, 1], "r": 2, "multicast": "coded",
              "combine_impl": "xla"}


def main(mode: str) -> None:
    if mode == "no_exchange":
        # every all_to_all returns the sender's own blocks: what a shuffle
        # that never crosses chips would hand on
        jax.lax.all_to_all = lambda x, *a, **k: x
    cell = load_cell("wordcount.1chip")
    cell = dataclasses.replace(
        cell, chips=4, workload=FOUR_CHIPS,
        config=dict(cell.config, N=12, tokens_per_subfile=1024))
    result = run_cell(cell, 2**31 + 3, 0.2, False, time.perf_counter(),
                      log=lambda *_: None)
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1])
