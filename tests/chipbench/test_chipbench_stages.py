"""The engine's tracing on a trace recorded on a TPU v5e: one chip running
a one-second traced window of the ``wordcount.1chip`` cell, kept by
``keep_wordcount_trace.py --seconds 1`` (``wordcount_1chip.xplane.pb``)
with the fused program's compiled HLO text from the same run
(``wordcount_1chip_fused.hlo.txt``).  The key rule of
``repro.obs.tracing.op_key`` must name the chip's ops from the compiled
text, and the ``map_device_ms`` reader must read the trace.

Reading the recorded trace needs JAX's ``ProfileData`` only: no TPU
library is loaded."""
import pathlib
import types

import pytest

from chipbench import trace
from chipbench.harness import load_module
from repro.mapreduce import engine
from repro.obs.tracing import op_key, op_stages

HERE = pathlib.Path(__file__).resolve().parent
XPLANE = str(HERE / "wordcount_1chip.xplane.pb")
HLO = HERE / "wordcount_1chip_fused.hlo.txt"
KEEP = load_module(str(HERE / "keep_wordcount_trace.py"), "keep_trace")
METRICS = HERE.parents[1] / "chipbench" / "metrics"


def _table():
    return op_stages(HLO.read_text(), engine.FUSED_STAGES)


def test_key_rule_attributes_the_fused_programs_device_time():
    table = _table()
    ops = KEEP.fused_ops(XPLANE)
    total = sum(t for _, t, _ in ops)
    labelled = sum(t for text, t, _ in ops if op_key(text) in table)
    assert labelled >= 0.99 * total > 0
    # the scatter-add, nearly all of a job's device time, is the map's
    by_op = {}
    for text, t, _ in ops:
        by_op[text] = by_op.get(text, 0.0) + t
    top = max(by_op, key=by_op.get)
    assert by_op[top] > 0.9 * total
    assert table[op_key(top)] == "map"


def test_map_reader_reads_the_kept_trace(monkeypatch):
    table = _table()
    monkeypatch.setattr(engine, "fused_op_stages", lambda: table)
    ops = KEEP.fused_ops(XPLANE)
    jobs = len({run for _, _, run in ops})
    want = sum(t for text, t, _ in ops
               if table.get(op_key(text)) == "map") / jobs * 1e3
    # one window from the sync mark on covers the whole traced run
    s = trace.summarize(XPLANE, 0.0, [(0.0, 1e3)], [], 1)
    w = types.SimpleNamespace(trace=s, device_kind="TPU v5 lite", jobs=jobs,
                              spans={})
    reader = load_module(str(METRICS / "map_device_ms.py"), "r_map_kept")
    got = reader.read(w)
    assert got == pytest.approx(want, rel=1e-9)
    assert 100.0 < got < 400.0


def test_kept_trace_holds_one_annotation_per_engine_span_in_order():
    from jax.profiler import ProfileData
    notes = sorted((e.start_ns, e.name)
                   for plane in ProfileData.from_file(XPLANE).planes
                   for line in plane.lines for e in line.events
                   if e.name.startswith("engine_phase:"))
    names = [n.split(":", 1)[1] for _, n in notes]
    phases = ["plan_compile", "pack", "upload", "map_shuffle_reduce",
              "assemble", "account"]
    jobs = len(names) // len(phases)
    assert jobs >= 1 and names == phases * jobs


def test_new_span_and_stage_readers_per_job(monkeypatch):
    scatter = "%fusion.1 = f32[1000,1]{1,0} fusion(s32[1,32,8]{2,1,0} %p)"
    joint = "%fusion.2 = f32[1000,1]{1,0} fusion(f32[1000,1]{1,0} %fusion.1)"
    ops = {scatter: [0.8e9, 4], joint: [0.1e9, 4], "%copy.3 = f32[2] copy(":
           [0.2e9, 4]}
    s = trace.Summary(4e9, [1e9, 3e9], [ops, dict(ops)], {})
    monkeypatch.setattr(engine, "fused_op_stages", lambda: {
        "fusion.1 = f32[1000,1] fusion": "map",
        "fusion.2 = f32[1000,1] fusion": "map+reduce"})
    w = types.SimpleNamespace(trace=s, device_kind="TPU v5 lite", jobs=4,
                              spans={"pack": 0.4, "map_shuffle_reduce": 2.0,
                                     "upload": 0.08, "assemble": 0.06})
    read = {n: load_module(str(METRICS / f"{n}.py"), f"r_new_{n}").read(w)
            for n in ("upload_span_ms", "assemble_span_ms", "map_device_ms")}
    assert read == pytest.approx({"upload_span_ms": 20.0,
                                  "assemble_span_ms": 15.0,
                                  # 0.8 s a chip over 4 jobs; the joint op
                                  # counts under neither stage
                                  "map_device_ms": 200.0})


def test_stage_reader_reads_nothing_from_a_program_without_the_table(
        monkeypatch):
    reader = load_module(str(METRICS / "map_device_ms.py"),
                         "r_map_device_parent")
    s = trace.Summary(4e9, [1e9], [{"%fusion.1 = f32[2] fusion(": [1, 1]}],
                      {})
    w = types.SimpleNamespace(trace=s, device_kind="TPU v5 lite", jobs=4,
                              spans={})
    monkeypatch.delattr(engine, "fused_op_stages")
    assert reader.read(w) is None
    monkeypatch.setattr(engine, "fused_op_stages", dict, raising=False)
    assert reader.read(w) is None           # no fused program recorded
