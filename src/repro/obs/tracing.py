"""Structured span/event tracing with a stable schema + exporters.

One event type serves all three layers:

  * the **simulator** replaces its bare ``(now, kind, tuple)`` trace entries
    with :class:`TraceEvent` (a compatibility shim on
    :class:`repro.sim.ClusterSim` keeps the legacy tuple view alive);
  * the **engine** wraps its phases (plan compile, host pack, upload, the
    jitted fused program, output assembly, byte accounting) in spans via the
    process-global tracer, which also opens a profiler ``TraceAnnotation``
    per span so the spans land on the device trace's clock, and
    :func:`spans_from_phase_timings` converts the calibrated per-phase
    device timings of ``measure_phase_timings`` into spans;
  * the **scheduler** emits admission / decision / drain events into the
    cluster tracer it runs on.

Timestamps are EXACT where recorded (the simulator trace must compare
bit-identically across seeded reruns, and consumers like the resume test
need exact event times); rounding happens only in the exporters, so
committed artifacts (golden files, BENCH JSON) stay stable without
perturbing live consumers.

Exporters:

  * :func:`to_jsonl` — one JSON object per line, sorted keys;
  * :func:`to_chrome_trace` — Chrome/Perfetto ``trace_event`` format
    (``{"traceEvents": [...]}``).  Open the file at ``chrome://tracing`` or
    https://ui.perfetto.dev: spans render as nested bars per (pid=job,
    tid=phase lane), instants as marks.  Sim time is seconds and is scaled
    to microseconds on export; engine spans use wall-clock seconds, same
    scaling.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import re
import time
from typing import (Any, Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple, Union)

TS_NDIGITS = 12          # exporter-side rounding (float-stable artifacts)


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One structured trace record — the stable schema of the whole system.

    ``ts`` is seconds (sim clock or wall clock, per tracer); ``kind`` is the
    event type (the simulator's event kinds, ``"span"`` for timed spans,
    scheduler ``"sched_*"`` kinds...); ``job_id``/``phase`` are filled where
    the producer knows them; ``labels`` is a sorted tuple of (key, str)
    pairs so events stay hashable and compare deterministically; ``dur`` is
    span duration in seconds (None for instants); ``data`` carries the
    legacy positional payload of the simulator's tuple trace.
    """
    ts: float
    kind: str
    job_id: Optional[int] = None
    phase: Optional[str] = None
    labels: Tuple[Tuple[str, str], ...] = ()
    dur: Optional[float] = None
    data: Tuple[Any, ...] = ()

    def to_dict(self, ndigits: Optional[int] = TS_NDIGITS) -> Dict[str, Any]:
        rnd = (lambda x: x) if ndigits is None else \
            (lambda x: round(float(x), ndigits))
        out: Dict[str, Any] = {"ts": rnd(self.ts), "kind": self.kind}
        if self.job_id is not None:
            out["job_id"] = self.job_id
        if self.phase is not None:
            out["phase"] = self.phase
        if self.labels:
            out["labels"] = dict(self.labels)
        if self.dur is not None:
            out["dur"] = rnd(self.dur)
        if self.data:
            out["data"] = _jsonable(self.data)
        return out


def _jsonable(x: Any) -> Any:
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in sorted(x.items())}
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    if hasattr(x, "item"):                    # numpy scalar
        return x.item()
    return str(x)


def _labels_of(labels: Dict[str, Any]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class Tracer:
    """Append-only event collector with an injectable clock.

    ``enabled=False`` turns every record call into a near-no-op (one
    attribute check), so instrumented hot paths cost nothing when tracing
    is off — the engine's process-global tracer ships disabled and is
    switched on per run/bench via :func:`enable_tracing`.

    ``annotate=True`` (the process-global tracer only) makes every enabled
    :meth:`span` also open a ``jax.profiler.TraceAnnotation`` named
    ``<kind>:<phase>``, so a profiler trace taken meanwhile shows the span
    on its host plane, on the device ops' clock.  Tracers on an injected
    (simulated) clock never annotate.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 enabled: bool = True, annotate: bool = False) -> None:
        self.clock = clock if clock is not None else time.perf_counter
        self.enabled = enabled
        self.annotate = annotate
        self.events: List[TraceEvent] = []

    def event(self, kind: str, job_id: Optional[int] = None,
              phase: Optional[str] = None, data: Tuple[Any, ...] = (),
              ts: Optional[float] = None, **labels: Any) -> None:
        """Record an instant event (no-op when disabled)."""
        if not self.enabled:
            return
        self.events.append(TraceEvent(
            self.clock() if ts is None else float(ts), kind, job_id, phase,
            _labels_of(labels), None, tuple(data)))

    def span_at(self, start: float, end: float, kind: str = "span",
                job_id: Optional[int] = None, phase: Optional[str] = None,
                data: Tuple[Any, ...] = (), **labels: Any) -> None:
        """Record a completed span with explicit bounds (the simulator knows
        its phase start/end times; no wall clock involved)."""
        if not self.enabled:
            return
        self.events.append(TraceEvent(
            float(start), kind, job_id, phase, _labels_of(labels),
            float(end) - float(start), tuple(data)))

    @contextlib.contextmanager
    def span(self, phase: str, job_id: Optional[int] = None,
             kind: str = "span", **labels: Any):
        """Context manager measuring a wall-clock span around its body."""
        if not self.enabled:
            yield self
            return
        with _annotation(f"{kind}:{phase}") if self.annotate else \
                contextlib.nullcontext():
            t0 = self.clock()
            try:
                yield self
            finally:
                self.span_at(t0, self.clock(), kind, job_id, phase, **labels)

    def clear(self) -> None:
        self.events.clear()


def _annotation(name: str):
    import jax.profiler            # lazily: this module works without JAX
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# Process-global tracer (engine + anything without its own clock)
# ---------------------------------------------------------------------------

_TRACER = Tracer(enabled=False, annotate=True)


def get_tracer() -> Tracer:
    """The process-global tracer.  Disabled by default: enabling it is the
    observability switch for the engine's host-side spans."""
    return _TRACER


def enable_tracing(enabled: bool = True) -> Tracer:
    """Toggle the global tracer; returns it (cleared on enable so a fresh
    run starts with an empty buffer)."""
    _TRACER.enabled = enabled
    if enabled:
        _TRACER.clear()
    return _TRACER


# ---------------------------------------------------------------------------
# Span adapters
# ---------------------------------------------------------------------------

def spans_from_phase_timings(row: Dict[str, Any],
                             tracer: Optional[Tracer] = None,
                             job_id: Optional[int] = None) -> List[TraceEvent]:
    """Convert one ``measure_phase_timings`` row (the calibration feed of
    :func:`repro.mapreduce.engine.measure_phase_timings`) into consecutive
    per-phase device-timing spans, recorded on ``tracer`` (default: the
    global one) and returned.

    The row's phases are laid end to end from t=0 — these are best-of
    per-phase device timings, not one wall-clock run, so the produced
    timeline is the *idealized* pipeline the calibration fit consumes (and
    exactly what the simulator's cost model reproduces)."""
    tracer = tracer if tracer is not None else _TRACER
    meta = {str(k): v for k, v in row.get("meta", {}).items()}
    t = 0.0
    out: List[TraceEvent] = []
    phases = dict(row["seconds"])
    if "shuffle_s" in meta:                  # measured but reported in meta
        phases["shuffle"] = float(meta["shuffle_s"])
    for phase in ("plan_compile", "map", "pack", "shuffle", "reduce"):
        if phase not in phases:
            continue
        dur = float(phases[phase])
        ev = TraceEvent(t, "device_phase", job_id, phase,
                        _labels_of({"job": meta.get("job", ""),
                                    "backend": meta.get("backend", "")}),
                        dur)
        out.append(ev)
        t += dur
    if tracer.enabled:
        tracer.events.extend(out)
    return out


# ---------------------------------------------------------------------------
# Device ops -> named stages, from a compiled module's HLO text
# ---------------------------------------------------------------------------

# "[ROOT ]%name = <result shape> opcode(": the part of an instruction that
# both a compiled module's text and a device trace's op name print alike
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = (.*?) ([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_APPLIES = re.compile(r"to_apply=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
# a scope inside a transform is named through it: "vmap(reducer_sort)"
_TRANSFORMED = re.compile(r"^(?:[\w\-]+\()+([^()]*)\)+$")


def op_key(text: str) -> Optional[str]:
    """The key of one HLO instruction, from a line of module text or a
    device trace's op name: ``"<name> = <result shape> <opcode>"`` with
    layouts dropped (``%fusion.1 = f32[1000,1]{1,0:T(8,128)} fusion(s32[..``
    -> ``"fusion.1 = f32[1000,1] fusion"``).  None for any other text."""
    m = _INSTR.match(text)
    if m is None:
        return None
    return f"{m.group(1)} = {_LAYOUT.sub('', m.group(2))} {m.group(3)}"


def _operands(line: str, start: int) -> List[str]:
    """Names of the operands in the parentheses opening at ``start``."""
    depth = 0
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                return _OPERAND.findall(line, start, i)
    return []


def _scope(component: str) -> str:
    """A name-stack component with its transform wrappers dropped."""
    m = _TRANSFORMED.match(component)
    return m.group(1) if m else component


def op_stages(hlo_text: str, stages: Sequence[str]) -> Dict[str, str]:
    """``{op_key: stage}`` for the instructions of a compiled module, by
    the ``stages`` (``jax.named_scope`` names) their metadata names.

    An instruction's scope paths are the lists of ``stages`` names in its
    ``op_name``, outermost first, a scope opened inside a transform counting
    by its own name (``vmap(reducer_sort)`` is ``reducer_sort``), one per
    ``;``-joined part (the compiler
    joins the names of instructions it merges); a fusion's paths are its
    own and those of every instruction in the computations it calls.
    Paths that another path extends are dropped, and the innermost name of
    each path left is a stage of the instruction.  An instruction whose metadata names no stage
    (the compiler drops it on some rewrites, e.g. the TPU's scatter) takes
    the stages of the instructions whose results it reads.  One stage is
    the label; more give the joint label ``"a+b"`` in ``stages`` order.
    Instructions inside fused computations and reducers get no key of their
    own: a device trace never shows them."""
    comps: Dict[str, list] = {}          # computation -> its instructions
    body: list = []
    for line in hlo_text.splitlines():
        head = _HEADER.match(line)
        if head:
            body = comps.setdefault(head.group(1), [])
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name = _OP_NAME.search(line)
        own = {tuple(c for c in map(_scope, part.split("/")) if c in stages)
               for part in (name.group(1) if name else "").split(";")}
        body.append((m.group(1), op_key(line), own - {()},
                     _CALLS.findall(line), _operands(line, m.end() - 1)))

    def paths(own, calls):
        out = set(own)
        for c in calls:
            for _, _, p, cc, _ in comps.get(c, ()):
                out |= paths(p, cc)
        return out

    fused = {c for instrs in comps.values() for *_, cs, _ in instrs
             for c in cs} | set(_APPLIES.findall(hlo_text))
    table: Dict[str, str] = {}
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        of: Dict[str, set] = {}            # instruction -> its stages
        for name, key, own, calls, operands in instrs:
            found = paths(own, calls)
            inner = {p[-1] for p in found
                     if not any(q[:len(p)] == p and q != p for q in found)}
            of[name] = inner or set().union(*(of.get(o, set())
                                              for o in operands))
            if of[name]:
                table[key] = "+".join(s for s in stages if s in of[name])
    return table


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def to_jsonl(events: Iterable[TraceEvent], path: Optional[str] = None,
             ndigits: Optional[int] = TS_NDIGITS) -> str:
    """JSONL export (one event per line, sorted keys, timestamps rounded to
    ``ndigits`` — rounding lives HERE, not in the producers, so committed
    artifacts are stable while live consumers see exact times)."""
    lines = [json.dumps(e.to_dict(ndigits), sort_keys=True) for e in events]
    text = "\n".join(lines) + ("\n" if lines else "")
    if path is not None:
        with open(path, "w") as f:
            f.write(text)
    return text


def to_chrome_trace(events: Iterable[TraceEvent],
                    path: Optional[str] = None,
                    time_scale: float = 1e6) -> Dict[str, Any]:
    """Chrome/Perfetto ``trace_event`` export.

    Spans (``dur`` set) become complete events (``ph="X"``), instants become
    ``ph="i"`` with thread scope.  ``pid`` is the job id (-1 for cluster-
    scope events), ``tid`` the phase lane (falling back to the kind), and
    timestamps are scaled seconds -> microseconds (``time_scale``).  Load
    the written file in ``chrome://tracing`` or https://ui.perfetto.dev.
    """
    te: List[Dict[str, Any]] = []
    for e in events:
        pid = -1 if e.job_id is None else int(e.job_id)
        tid = e.phase if e.phase is not None else e.kind
        args = dict(e.labels)
        if e.data:
            args["data"] = json.dumps(_jsonable(e.data))
        rec: Dict[str, Any] = {
            "name": e.kind if e.phase is None else f"{e.kind}:{e.phase}",
            "cat": e.kind,
            "ts": round(e.ts * time_scale, 3),
            "pid": pid,
            "tid": tid,
            "args": args,
        }
        if e.dur is not None:
            rec["ph"] = "X"
            rec["dur"] = round(e.dur * time_scale, 3)
        else:
            rec["ph"] = "i"
            rec["s"] = "t"
        te.append(rec)
    doc = {"traceEvents": te, "displayTimeUnit": "ms"}
    if path is not None:
        with open(path, "w") as f:
            json.dump(doc, f, sort_keys=True)
    return doc


def validate_chrome_trace(doc: Union[Dict[str, Any], str]) -> int:
    """Sanity-check a ``trace_event`` document (dict or JSON text): required
    keys present, numeric timestamps, known phase codes.  Returns the event
    count; raises ``ValueError`` on malformed input.  Used by the bench to
    assert exported traces really load."""
    if isinstance(doc, str):
        doc = json.loads(doc)
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError("missing traceEvents list")
    for i, e in enumerate(events):
        for k in ("name", "ph", "ts", "pid", "tid"):
            if k not in e:
                raise ValueError(f"traceEvents[{i}] missing {k!r}")
        if not isinstance(e["ts"], (int, float)):
            raise ValueError(f"traceEvents[{i}].ts not numeric")
        if e["ph"] not in ("X", "i", "B", "E", "M"):
            raise ValueError(f"traceEvents[{i}].ph unknown: {e['ph']!r}")
        if e["ph"] == "X" and not isinstance(e.get("dur"), (int, float)):
            raise ValueError(f"traceEvents[{i}] span without numeric dur")
    return len(events)


__all__ = [
    "TraceEvent", "Tracer", "get_tracer", "enable_tracing",
    "spans_from_phase_timings", "op_key", "op_stages", "to_jsonl",
    "to_chrome_trace", "validate_chrome_trace", "TS_NDIGITS",
]
