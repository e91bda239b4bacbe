"""One traced run of a cell on the chip, kept whole: the window's
``.xplane.pb``, the fused program's compiled HLO text, the engine spans,
and three checks of the engine's tracing, printed as the last line (JSON).

    python3 tests/chipbench/keep_wordcount_trace.py --seed <n> \\
        --seconds <s> --out DIR [--workload wordcount.1chip]

The checks:

* clock: each engine span's start, put on the trace's clock through the
  harness's ``chipbench_sync`` mark, against the start of its
  ``engine_phase:<phase>`` annotation in the trace (offset in ns: first,
  last, largest, and its drift per second of window);
* tiling: the engine spans of the jobs summed, against the summed job time;
* stages: the device time of the fused program's ops (the ``XLA Ops``
  events inside its ``XLA Modules`` runs), by the label that
  ``engine.fused_op_stages()`` gives each op.

``test_chipbench_stages.py`` reads the ``.xplane.pb`` and HLO text of a
one-second run kept this way.  Exits 1 without a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

PHASES = ("pack", "upload", "map_shuffle_reduce", "assemble", "account")


def clock_offsets(xplane: str, sync_t: float, spans) -> dict:
    """Annotation start minus span start mapped through the sync mark, ns,
    span by span in time order."""
    from jax.profiler import ProfileData
    from chipbench.trace import SYNC_NAME
    notes, sync_ns = [], None
    for plane in ProfileData.from_file(xplane).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine_phase:"):
                    notes.append((e.start_ns, e.name))
                elif e.name == SYNC_NAME and sync_ns is None:
                    sync_ns = e.start_ns
    notes.sort()
    spans = sorted(spans, key=lambda e: e.ts)
    if [n for _, n in notes] != [f"engine_phase:{e.phase}" for e in spans]:
        return {"matched": False, "annotations": len(notes),
                "spans": len(spans)}
    t = [e.ts - sync_t for e in spans]
    off = [a - (sync_ns + s * 1e9) for (a, _), s in zip(notes, t)]
    n = len(t)
    mt, mo = sum(t) / n, sum(off) / n
    var = sum((x - mt) ** 2 for x in t)
    slope = (sum((x - mt) * (y - mo) for x, y in zip(t, off)) / var
             if var else 0.0)
    return {"matched": True, "spans": n, "first_ns": off[0],
            "last_ns": off[-1], "max_abs_ns": max(abs(o) for o in off),
            "drift_ns_per_s": slope, "window_from_sync_s": [t[0], t[-1]]}


def fused_ops(xplane: str, chip: int = 0):
    """(op text, device seconds, module run) of every op that ran inside a
    run of the fused program (``XLA Modules`` events ``jit_device_fn``) on
    one chip of a kept trace."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name != f"/device:TPU:{chip}":
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns)
                      for e in lines.get("XLA Modules", ())
                      if e.name.startswith("jit_device_fn("))
        for e in lines.get("XLA Ops", ()):
            run = next((i for i, (a, b) in enumerate(runs)
                        if a <= e.start_ns < b), None)
            if run is not None:
                out.append((e.name, e.duration_ns / 1e9, run))
    return out


def stage_times(xplane: str, table: dict) -> dict:
    """Device seconds of the fused program's ops by stage label, chip 0."""
    from chipbench.trace import op_label
    from repro.obs.tracing import op_key
    by_label, by_op, runs = {}, {}, set()
    for text, t, run in fused_ops(xplane):
        runs.add(run)
        label = table.get(op_key(text)) or "unlabelled"
        by_label[label] = by_label.get(label, 0.0) + t
        name = op_label(text)
        by_op[name] = (label, by_op.get(name, (None, 0.0))[1] + t)
    top = sorted(by_op.items(), key=lambda kv: -kv[1][1])[:8]
    return {"module_runs": len(runs), "seconds_by_label": by_label,
            "top_ops": [[n, lab, t] for n, (lab, t) in top]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="wordcount.1chip")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("keep_wordcount_trace: no TPU", file=sys.stderr)
        return 1
    from chipbench import trace as trace_lib
    from chipbench.harness import load_cell, run_cell
    from repro.compile_cache import enable_compile_cache
    from repro.mapreduce import engine
    from repro.obs.tracing import get_tracer
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    sync = []
    real_sync_mark = trace_lib.sync_mark

    def sync_mark():
        sync.append(real_sync_mark())
        return sync[-1]
    trace_lib.sync_mark = sync_mark
    lines = []

    def log(*a):
        lines.append(" ".join(map(str, a)))
        print(*a, flush=True)

    os.makedirs(args.out, exist_ok=True)
    kept = os.path.join(args.out, "kept")
    result = run_cell(load_cell(args.workload), args.seed, args.seconds,
                      True, T_START, keep_trace=kept, log=log)
    xplane = os.path.join(args.out, f"{args.workload}.xplane.pb")
    shutil.move(glob.glob(os.path.join(kept, "*.xplane.pb"))[0], xplane)
    shutil.rmtree(kept)

    texts = sorted({exe.lower(spec).compile().as_text()
                    for (exe, _, _), spec in engine._FUSED_CALLS.items()})
    for i, text in enumerate(texts):
        with open(os.path.join(args.out, f"{args.workload}.fused{i}.hlo.txt"),
                  "w") as f:
            f.write(text)
    spans = [e for e in get_tracer().events if e.kind == "engine_phase"]
    with open(os.path.join(args.out, "spans.jsonl"), "w") as f:
        for e in spans:
            f.write(json.dumps({"phase": e.phase, "ts": e.ts, "dur": e.dur})
                    + "\n")
    times = json.loads(next(s for s in lines
                            if s.startswith("job seconds: "))[13:])
    in_jobs = sum(e.dur for e in spans if e.phase in PHASES)
    by_phase = {}
    for e in spans:
        by_phase[e.phase] = by_phase.get(e.phase, 0.0) + e.dur
    table = engine.fused_op_stages()
    checks = {
        "fused_programs": len(texts),
        "clock": clock_offsets(xplane, sync[0], spans),
        "tiling": {"jobs": len(times), "job_s": sum(times),
                   "spans_s": in_jobs, "share": in_jobs / sum(times),
                   "by_phase_s": by_phase},
        "stages": stage_times(xplane, table),
        "result": result,
    }
    with open(os.path.join(args.out, "checks.json"), "w") as f:
        json.dump(checks, f, indent=1)
    print(json.dumps(checks), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
