"""Reduce a JAX profiler trace (``.xplane.pb``) of the measured window to
what the per-layer metrics read: per-chip busy time, per-op device time by
name, and the device's idle time split by what the host was doing.

Only the job windows count: the trace also holds the harness making the
next job's input, which is no part of a job.  Host times (``perf_counter``)
are put on the trace's clock through one annotation, ``chipbench_sync``,
recorded at a known host time when tracing starts.

Reading a trace needs nothing but JAX's ``ProfileData``; no TPU library.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import time
from typing import Callable, Dict, List, Sequence, Tuple

SYNC_NAME = "chipbench_sync"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")

Interval = Tuple[float, float]


def sync_mark() -> float:
    """Record the sync annotation; returns its host time."""
    import jax
    with jax.profiler.TraceAnnotation(SYNC_NAME):
        return time.perf_counter()


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


@dataclasses.dataclass
class DeviceOps:
    """One chip's device operations: (HLO text, start_ns, end_ns)."""
    chip: int
    ops: List[Tuple[str, float, float]]


def read_xplane(path: str) -> Tuple[float, List[DeviceOps]]:
    """The sync annotation's start (ns, trace clock) and every chip's ops
    from its ``XLA Ops`` line, chips in id order."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    sync_ns = None
    chips: List[DeviceOps] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                chips.append(DeviceOps(int(m.group(1)), [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]))
            elif not m and sync_ns is None:
                for e in line.events:
                    if e.name == SYNC_NAME:
                        sync_ns = e.start_ns
                        break
    if sync_ns is None:
        raise ValueError(f"{path}: no {SYNC_NAME} annotation")
    chips.sort(key=lambda c: c.chip)
    return sync_ns, chips


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]
              ) -> List[Interval]:
    """Pairwise overlap of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(xs: Sequence[Interval]) -> float:
    return sum(b - a for a, b in xs)


def complement(xs: Sequence[Interval], within: Sequence[Interval]
               ) -> List[Interval]:
    """The parts of ``within`` that ``xs`` leaves uncovered; both merged
    and sorted."""
    out, i = [], 0
    for a, b in within:
        t = a
        while i < len(xs) and xs[i][1] <= t:
            i += 1
        j = i
        while j < len(xs) and xs[j][0] < b:
            if xs[j][0] > t:
                out.append((t, xs[j][0]))
            t = max(t, xs[j][1])
            j += 1
        if t < b:
            out.append((t, b))
    return out


def overlap(a: float, b: float, windows: Sequence[Interval],
            starts: Sequence[float]) -> float:
    """Length of [a, b) inside merged, sorted ``windows`` (``starts``:
    their start points)."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    t = 0.0
    while i < len(windows) and windows[i][0] < b:
        t += max(0.0, min(b, windows[i][1]) - max(a, windows[i][0]))
        i += 1
    return t


@dataclasses.dataclass
class Summary:
    """The traced window, per chip, on the trace's clock (ns)."""
    window_ns: float                     # summed job time
    busy_ns: List[float]                 # per chip: union of ops in jobs
    # per chip: op -> [device ns in jobs, calls]; an op is named by its
    # HLO instruction text, result and operand shapes included
    op_ns: List[Dict[str, List[float]]]
    idle_by_host: Dict[str, float]       # host activity -> idle ns, mean

    @property
    def chips(self) -> int:
        return len(self.busy_ns)

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns) / self.chips / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def ops(self, match: Callable[[str], bool]
            ) -> List[Tuple[str, float, float]]:
        """(op text, device seconds, calls) of the matching ops, summed
        over chips."""
        out: Dict[str, List[float]] = {}
        for per_chip in self.op_ns:
            for text, (t, n) in per_chip.items():
                if match(text):
                    acc = out.setdefault(text, [0.0, 0.0])
                    acc[0] += t / 1e9
                    acc[1] += n
        return [(text, t, n) for text, (t, n) in out.items()]

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time and the idle time by what
        the host was doing, in seconds per chip (mean over chips)."""
        ops: Dict[str, float] = {}
        for text, t, _ in self.ops(lambda _: True):
            label = op_label(text)
            ops[label] = ops.get(label, 0.0) + t / self.chips
        gaps = {k: v / 1e9 for k, v in self.idle_by_host.items()}
        return {
            "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
            "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top],
        }


def op_label(text: str) -> str:
    """Short name of an op: its instruction name and opcode, with the
    fusion kind (``%fusion.1 fusion kCustom``)."""
    name = text.split(" = ", 1)[0]
    op = _OPCODE.search(text)
    kind = _KIND.search(text)
    return " ".join(filter(None, [name, op and op.group(1),
                                  kind and kind.group(1)]))


def host_activities(jobs: Sequence[Interval], spans) -> Dict[str, list]:
    """Host intervals (perf_counter seconds) by activity: the engine's
    ``engine_phase`` spans; the time of a job before its first span
    (``call_entry``); and the rest of a job outside the spans
    (``after_spans``: output assembly and byte accounting, mostly)."""
    acts: Dict[str, list] = {}
    for e in spans:
        acts.setdefault(e.phase, []).append((e.ts, e.ts + e.dur))
    covered = union([iv for ivs in acts.values() for iv in ivs])
    for j0, j1 in jobs:
        for a, b in complement(covered, [(j0, j1)]):
            started = any(j0 <= s0 and s1 <= a for s0, s1 in covered)
            acts.setdefault("after_spans" if started else "call_entry",
                            []).append((a, b))
    return acts


def summarize(path: str, sync_t: float, jobs: Sequence[Interval], spans,
              n_devices: int) -> Summary:
    """Reduce the trace at ``path`` over the host job intervals ``jobs``
    (perf_counter seconds), with the engine spans for idle attribution."""
    sync_ns, chips = read_xplane(path)
    if len(chips) < n_devices:
        raise ValueError(f"{path}: {len(chips)} TPU planes with ops, "
                         f"{n_devices} expected")

    def to_ns(ivs):
        return [(sync_ns + (a - sync_t) * 1e9, sync_ns + (b - sync_t) * 1e9)
                for a, b in ivs]
    windows = union(to_ns(jobs))
    starts = [a for a, _ in windows]
    acts = {k: union(to_ns(v))
            for k, v in host_activities(jobs, spans).items()}
    busy, per_op = [], []
    idle_by_host: Dict[str, float] = {}
    for chip in chips[:n_devices]:
        ops: Dict[str, List[float]] = {}
        for text, a, b in chip.ops:
            t = overlap(a, b, windows, starts)
            if t:
                acc = ops.setdefault(text, [0.0, 0])
                acc[0] += t
                acc[1] += 1
        on = intersect(union([(a, b) for _, a, b in chip.ops]), windows)
        idle = complement(on, windows)
        for act, ivs in acts.items():
            idle_by_host[act] = idle_by_host.get(act, 0.0) + length(
                intersect(idle, ivs)) / n_devices
        busy.append(length(on))
        per_op.append(ops)
    return Summary(length(windows), busy, per_op, idle_by_host)
