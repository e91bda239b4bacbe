"""The control of the comparison that decides ``correct``: the plain
reference computed one precision down (bfloat16 for the configurations'
float32), put in the program's place, at the cell's own size.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3 [--jobs 3]

For each seed it compares the control's answers for the window's first
``--jobs`` jobs with the float32 reference, exactly as a run compares the
program's, and prints the readings.  A control that passes would make the
comparison worthless; the upper reading of each limit comes from here.
"""
import argparse
import json
import os
import sys

sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or ".") != os.path.dirname(
                   os.path.abspath(__file__))]
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench.harness import base_tokens, compare, load_cell  # noqa: E402


def control_readings(cell, seed: int, jobs: int) -> dict:
    ref, cfg = cell.reference, cell.config
    base = base_tokens(cfg, seed)
    state = ref.prepare(base, cfg)
    got = [ref.control(base, k, cfg) for k in range(1, jobs + 1)]
    want = [ref.expected(state, k, cfg) for k in range(1, jobs + 1)]
    return compare(got, want)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--jobs", type=int, default=3)
    args = ap.parse_args()
    cell = load_cell(args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": cell.name, "seed": seed,
                          **control_readings(cell, seed, args.jobs)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
