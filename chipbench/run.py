"""Chip benchmark of the MapReduce engine: one cell, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs the cell named in ``BENCHMARK.json`` on the TPU chips of this host
(none found: exit 1, no result).  Set-up makes the inputs from ``--seed``
and runs one whole job, which compiles every program of the cell; then a
closed loop with one client submits jobs back to back until ``--seconds``
of job time have passed.  The last line of stdout is the result as JSON:
the end-to-end metrics with ``--trace 0``, the per-layer metrics (from a
profiler trace and the engine's spans) with ``--trace 1``.  The numbers
compared with the plain reference are the last lines of stderr, each
beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# this directory holds a module named like the standard library's trace:
# import the benchmark as a package from the repo root instead
sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or ".") != os.path.dirname(
                   os.path.abspath(__file__))]
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the traced window's .xplane.pb into DIR")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    from chipbench.harness import load_cell, run_cell
    cell = load_cell(args.workload)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # keep every program, even those that compile in under a second, so
    # that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"compile cache: {cache}")
    print(f"device: {devices[0].device_kind} x {cell.chips}")

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, keep_trace=args.keep_trace)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
