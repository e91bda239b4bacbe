"""The benchmark's run of one cell: inputs from the seed, set-up, a closed
job loop over a measured window, the check against the plain reference,
and the result line.

Everything specific to a cell is data found by name: the cell's entry in
``BENCHMARK.json``, ``traffic/<traffic>.json`` (the mesh, r and shuffle
the jobs run under), ``configs/<config>.json`` (job, sizes),
``references/<job>.py`` (the plain NumPy reference of the job) and
``metrics/<metric>.py`` (one reader per per-layer metric).  Adding a cell,
a configuration or a metric adds files and entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")

# the largest |output - reference| a correct run may show: every value is
# an integer-valued float32 below 2^24, so the engine is exact
MAX_ABS_ERR_LIMIT = 0.0

# the keys a traffic file may hold: run_cell reads all of them but "what",
# and a key it would not read is refused rather than ignored
TRAFFIC_KEYS = {"what", "mesh", "r", "multicast", "combine_impl"}


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    workload: dict                 # traffic/<traffic>.json
    config: dict                   # configs/<config>.json
    end_to_end: List[dict]         # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]

    @property
    def reference(self):
        return load_module(os.path.join(
            HERE, "references", f"{self.config['job']}.py"),
            f"chipbench_ref_{self.config['job']}")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: str = BENCHMARK_JSON) -> Cell:
    bench = _load_json(bench_path)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"chipbench: no workload {name!r} in "
                         f"{bench_path}")
    wl = _load_json(os.path.join(HERE, "traffic",
                                 f"{entry['traffic']}.json"))
    if set(wl) != TRAFFIC_KEYS:
        raise SystemExit(f"chipbench: traffic {entry['traffic']} has keys "
                         f"{sorted(wl)}; the harness reads exactly "
                         f"{sorted(TRAFFIC_KEYS)}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    cfg = _load_json(os.path.join(ROOT, cfg_entry["file"]))
    if wl["mesh"][0] * wl["mesh"][1] != entry["chips"]:
        raise SystemExit(f"chipbench: traffic {entry['traffic']} runs on "
                         f"a {wl['mesh']} mesh, not on {entry['chips']} "
                         f"chip(s)")
    return Cell(name, entry["chips"], wl, cfg,
                [m for m in bench["end_to_end"] if _reports(m, name)],
                [m for m in bench["per_layer"] if _reports(m, name)])


# ---------------------------------------------------------------------------
# Inputs
# ---------------------------------------------------------------------------

def base_tokens(cfg: dict, seed: int) -> np.ndarray:
    """The run's [N, tokens_per_subfile] int32 tokens, uniform in
    [0, token_range), from the seed alone (any integer)."""
    rng = np.random.default_rng(seed % 2**64)
    return rng.integers(0, cfg["token_range"],
                        size=(cfg["N"], cfg["tokens_per_subfile"]),
                        dtype=np.int32)


def compare(got: List[np.ndarray], want: List[np.ndarray]) -> dict:
    """Numbers compared against the reference: the widest gap over all
    jobs, and how many jobs differ at all.  A missing job, a wrong shape
    or dtype, or a NaN reads as an infinite gap."""
    worst, wrong = 0.0, 0
    for g, w in zip(got, want):
        g = np.asarray(g)
        if g.shape != w.shape or g.dtype != w.dtype:
            gap = float("inf")
        else:
            diff = np.abs(g.astype(np.float64) - w.astype(np.float64))
            gap = float(np.nan_to_num(diff, nan=np.inf).max(initial=0.0))
        worst = max(worst, gap)
        wrong += gap > MAX_ABS_ERR_LIMIT
    missing = len(want) - len(got)
    if missing:
        worst, wrong = float("inf"), wrong + missing
    return {"max_abs_err": worst, "jobs_wrong": wrong}


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

class CompileCounter:
    """Backend compiles seen by JAX's monitoring events since creation."""

    def __init__(self) -> None:
        import jax
        self.count = 0
        self.seconds = 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += duration
        jax.monitoring.register_event_duration_secs_listener(listen)


@dataclasses.dataclass
class TracedWindow:
    """What a per-layer metric reader is given (``metrics/<name>.py``)."""
    cell: Cell
    jobs: int                      # jobs in the traced window
    spans: Dict[str, float]        # engine_phase span seconds, summed
    trace: object                  # trace.Summary of the window
    device_kind: str


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, keep_trace: Optional[str] = None,
             log=print) -> dict:
    """One run of ``cell``: returns the result object of the last line.

    The window is a closed loop with one client: job k + 1 is submitted
    when job k's outputs are ready, until ``seconds`` of job time have
    passed; the job in flight then finishes and counts.

    ``t_start`` is the process's start on ``time.perf_counter``'s clock;
    set-up is counted from it to the first timed job.  The caller has
    checked for the chips (tests drive a run on the CPU).  With
    ``keep_trace`` a traced run copies its ``.xplane.pb`` there.
    """
    import jax
    from repro.core.params import SchemeParams
    from repro.mapreduce import jobs as job_lib
    from repro.mapreduce.engine import run_job_distributed
    from repro.obs.tracing import enable_tracing
    from chipbench import trace as trace_lib

    devices = jax.devices()[:cell.chips]
    compiles = CompileCounter()

    wl, cfg = cell.workload, cell.config
    ref = cell.reference
    P_, Kr = wl["mesh"]
    params = SchemeParams(K=P_ * Kr, P=P_, Q=cfg["Q"], N=cfg["N"],
                          r=wl["r"])
    job = getattr(job_lib, cfg["job"])(**cfg["job_args"])
    mesh = jax.make_mesh((P_, Kr), ("rack", "server"), devices=devices)
    base = base_tokens(cfg, seed)
    input_bytes = base.nbytes

    def call(x):
        res = run_job_distributed(
            job, x, params, mesh, multicast=wl["multicast"],
            combine_impl=wl["combine_impl"])
        jax.block_until_ready(res.outputs)
        return res

    # set-up: the plan and every program of the cell, by one whole job
    warm = call(ref.job_input(base, 0, cfg))
    log(f"paper bytes per job: cross-rack "
        f"{warm.cross_rack_bytes * 4:.0f}, intra-rack "
        f"{warm.intra_rack_bytes * 4:.0f} (value-units x 4 B, "
        f"obs.bytes.plan_rack_bytes)")
    del warm
    warm_compiles = compiles.count
    # set-up's objects will never be freed: keep the collector's full
    # passes in the window from walking them
    gc.collect()
    gc.freeze()

    setup_s = time.perf_counter() - t_start
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        tracer = enable_tracing(True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host: annotations only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        sync = trace_lib.sync_mark()

    times: List[float] = []
    intervals: List[tuple] = []
    outputs: List[np.ndarray] = []
    k = 0
    while sum(times) < seconds:
        k += 1
        x = ref.job_input(base, k, cfg)         # new content, off the clock
        t0 = time.perf_counter()
        res = call(x)
        t1 = time.perf_counter()
        times.append(t1 - t0)
        intervals.append((t0, t1))
        outputs.append(np.asarray(res.outputs))
        del res, x
    window_compiles = compiles.count - warm_compiles
    gc.unfreeze()

    summary = None
    if trace:
        jax.profiler.stop_trace()
        spans: Dict[str, float] = {}
        span_events = [e for e in tracer.events if e.kind == "engine_phase"]
        for e in span_events:
            spans[e.phase] = spans.get(e.phase, 0.0) + float(e.dur)
        enable_tracing(False)
        xplane = trace_lib.find_xplane(trace_dir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, keep_trace)
        summary = trace_lib.summarize(xplane, sync, intervals, span_events,
                                      n_devices=len(devices))
    on_tpu = devices[0].platform == "tpu"
    peaks = [int(d.memory_stats()["peak_bytes_in_use"]) if on_tpu else 0
             for d in devices]

    log(f"jobs in window: {len(times)}; compiles in window: "
        f"{window_compiles}; set-up compiles: {warm_compiles} "
        f"({compiles.seconds:.6f} s of backend compile in all)")
    log("job seconds: " + json.dumps(times))
    log(f"memory_peak_bytes per chip: {peaks}")

    # the reference, once the window has closed
    state = ref.prepare(base, cfg)
    want = [ref.expected(state, i + 1, cfg) for i in range(len(outputs))]
    checks = compare(outputs, want)
    correct = (checks["max_abs_err"] <= MAX_ABS_ERR_LIMIT
               and len(outputs) > 0)

    result: dict = {"correct": bool(correct), "attempted": len(times),
                    "failed": int(checks["jobs_wrong"])}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": max(peaks)}
    if trace:
        ctx = TracedWindow(cell, len(times), spans, summary,
                           dev.device_kind)
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(os.path.join(HERE, "metrics",
                                              f"{m['name']}.py"),
                                 f"chipbench_metric_{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = summary.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = {
            "input_gib_per_s": input_bytes * len(times) / sum(times) / 2**30,
            # numpy's linear quantiles over every job of the window
            "jct_p50_s": float(np.quantile(times, 0.5)),
            "jct_p90_s": float(np.quantile(times, 0.9)),
            "setup_s": setup_s,
        }
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {n: {"value": v, "unit": units[n]}
                             for n, v in e2e.items() if n in units}
        result["device"] = device
    result["compared"] = {
        "max_abs_err": {"value": checks["max_abs_err"],
                        "limit": MAX_ABS_ERR_LIMIT},
        "jobs_wrong": {"value": checks["jobs_wrong"], "limit": 0},
    }
    return result
