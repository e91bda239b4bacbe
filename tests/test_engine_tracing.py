"""The engine's tracing: the ``engine_phase`` spans that tile a job, their
``TraceAnnotation`` twins in a profiler trace, and the table from the fused
program's ops to its named stages (``engine.fused_op_stages``), on the CPU
at tiny size."""
import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from repro.core.coded_collectives import compile_hybrid_plan
from repro.core.params import SchemeParams
from repro.mapreduce import engine
from repro.mapreduce.jobs import histogram_job
from repro.obs import tracing
from repro.sim import ClusterSim, RackTopology

ROOT = pathlib.Path(__file__).resolve().parent.parent
FUSED_PHASES = ["plan_compile", "pack", "upload", "map_shuffle_reduce",
                "assemble", "account"]
LEGACY_PHASES = ["plan_compile", "map", "pack", "shuffle", "reduce",
                 "assemble", "account"]
P1 = SchemeParams(K=1, P=1, Q=16, N=4, r=1)
JOB = histogram_job()


def _mesh():
    return jax.make_mesh((1, 1), ("rack", "server"),
                         devices=jax.devices()[:1])


def _subfiles():
    return np.random.default_rng(0).integers(
        0, 1000, size=(P1.N, 64)).astype(np.int32)


def _job(fused=True, subfiles=None):
    return engine.run_job_distributed(
        JOB, _subfiles() if subfiles is None else subfiles, P1, _mesh(),
        fused=fused)


@pytest.fixture
def traced():
    tracer = tracing.enable_tracing(True)
    try:
        yield tracer
    finally:
        tracing.enable_tracing(False)


@pytest.mark.parametrize("fused,phases", [(True, FUSED_PHASES),
                                          (False, LEGACY_PHASES)])
def test_traced_job_spans_tile_the_call(traced, fused, phases):
    _job(fused)                                  # compile outside the timing
    traced.clear()
    t0 = time.perf_counter()
    _job(fused)
    t1 = time.perf_counter()
    spans = [e for e in traced.events if e.kind == "engine_phase"]
    assert [e.phase for e in spans] == phases
    for a, b in zip(spans, spans[1:]):
        assert a.ts + a.dur <= b.ts              # no overlap, in order
    assert t0 <= spans[0].ts and spans[-1].ts + spans[-1].dur <= t1
    assert (t1 - t0) - sum(e.dur for e in spans) < 1e-3


def _count_calls(monkeypatch, owner, name):
    calls = []
    real = getattr(owner, name)

    def counted(*args, **kwargs):
        calls.append(name)
        return real(*args, **kwargs)
    monkeypatch.setattr(owner, name, counted)
    return calls


def test_untraced_job_records_nothing_and_adds_no_wait(monkeypatch):
    _job()
    waits = _count_calls(monkeypatch, jax, "block_until_ready")
    notes = _count_calls(monkeypatch, jax.profiler, "TraceAnnotation")

    def no_parse(*_):
        raise AssertionError("the job path parsed HLO")
    monkeypatch.setattr(engine, "op_stages", no_parse)
    tracer = tracing.get_tracer()
    n0 = len(tracer.events)
    res = _job()
    assert len(tracer.events) == n0 and res.blame is None
    # the wait on the fused program's output, as before the upload and
    # assemble spans
    assert len(waits) == 1
    assert notes == []
    tracing.enable_tracing(True)
    try:
        _job()
    finally:
        tracing.enable_tracing(False)
    assert len(waits) == 1 + 3                   # + upload and assemble
    assert len(notes) == len(FUSED_PHASES)


def _annotations(trace_dir):
    from jax.profiler import ProfileData
    path, = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    found = [(e.start_ns, e.name) for plane in data.planes
             for line in plane.lines for e in line.events
             if e.name.startswith("engine_phase:")]
    return [name for _, name in sorted(found)]


def test_engine_spans_annotate_the_profiler_trace(tmp_path):
    _job()
    with jax.profiler.trace(str(tmp_path / "off")):
        _job()
    tracer = tracing.enable_tracing(True)
    try:
        with jax.profiler.trace(str(tmp_path / "on")):
            _job()
        phases = [e.phase for e in tracer.events
                  if e.kind == "engine_phase"]
    finally:
        tracing.enable_tracing(False)
    assert phases == FUSED_PHASES
    assert _annotations(tmp_path / "on") == [f"engine_phase:{p}"
                                             for p in phases]
    assert _annotations(tmp_path / "off") == []


def test_simulator_tracers_never_annotate(monkeypatch):
    def no_annotation(_):
        raise AssertionError("a simulator span opened an annotation")
    monkeypatch.setattr(tracing, "_annotation", no_annotation)
    tr = tracing.Tracer(clock=lambda: 0.0)
    with tr.span("map", kind="engine_phase"):
        pass
    assert len(tr.events) == 1
    sim = ClusterSim(RackTopology(P=2, cross_bw=1e3, intra_bw=1e4), K=4)
    assert not sim.tracer.annotate
    assert tracing.get_tracer().annotate


# ---------------------------------------------------------------------------
# Stage table
# ---------------------------------------------------------------------------

HLO = """\
HloModule jit_device_fn, entry_computation_layout={(s32[1,4]{1,0})->f32[2]{0}}

%region_0 (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0), metadata={op_name="scatter-add"}
  %b = s32[] parameter(1), metadata={op_name="scatter-add"}
  ROOT %add.7 = s32[] add(%a, %b), metadata={op_name="jit(f)/map/add"}
}

%fused_computation (p0: f32[4]) -> f32[2] {
  %p0 = f32[4]{0} parameter(0)
  %z = f32[] constant(0)
  %s = f32[2]{0} reduce(%p0, %z), dimensions={0}, to_apply=%region_0, metadata={op_name="jit(f)/map/reduce_sum"}
  ROOT %r = f32[2]{0} negate(%s), metadata={op_name="jit(f)/reduce/neg"}
}

%fused_computation.1 (p1: f32[4]) -> f32[4] {
  %p1 = f32[4]{0} parameter(0)
  %e = f32[4]{0} negate(%p1), metadata={op_name="jit(f)/stage1/encode/neg"}
  ROOT %c = f32[4]{0} copy(%e), metadata={op_name="jit(f)/stage1/copy"}
}

ENTRY %main (x: s32[1,4]) -> f32[2] {
  %x = s32[1,4]{1,0} parameter(0), metadata={op_name="x"}
  %constant.1 = f32[] constant(0)
  %wrapped_scatter = f32[4]{0:T(128)} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/stage1/copy"}
  %all-to-all = (f32[4]{0}, f32[4]{0}) all-to-all(%wrapped_scatter, %wrapped_scatter), metadata={op_name="jit(f)/stage1/all_to_all"}
  %copy.2 = f32[4]{0} copy(%wrapped_scatter)
  %iota = s32[4]{0} iota(), iota_dimension=0
  %add.3 = f32[4]{0} add(%copy.2, %copy.2), metadata={op_name="jit(f)/map/mul;jit(f)/reduce/add"}
  ROOT %fusion.1 = f32[2]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/reduce/neg"}
}
"""


def test_op_key_matches_compiled_text_and_trace_text():
    compiled = ("  ROOT %fusion.1 = f32[1000,1]{1,0:T(8,128)} fusion(%p), "
                "kind=kCustom, calls=%fc")
    traced = ("%fusion.1 = f32[1000,1]{1,0:T(8,128)} fusion(s32[1,32,1048576]"
              "{2,1,0:T(8,128)} %param.1), kind=kCustom, calls=%fc")
    assert tracing.op_key(compiled) == tracing.op_key(traced) == \
        "fusion.1 = f32[1000,1] fusion"
    assert tracing.op_key(
        "%copy-start.4 = (s32[2]{0:T(128)S(1)}, u32[]{:S(2)}) copy-start("
        "s32[2]{0:T(128)} %constant.107)") == \
        "copy-start.4 = (s32[2], u32[]) copy-start"
    assert tracing.op_key("HloModule jit_device_fn") is None


def test_op_stages_innermost_and_joint_labels():
    table = tracing.op_stages(HLO, engine.FUSED_STAGES)
    assert table == {
        # stage1 around encode: the innermost, encode
        "wrapped_scatter = f32[4] fusion": "encode",
        "all-to-all = (f32[4], f32[4]) all-to-all": "stage1",
        # no stage in its metadata: the stage of what it reads (an op that
        # reads nothing with a stage, like the iota, gets no label)
        "copy.2 = f32[4] copy": "encode",
        # the names of two merged instructions, joined by the compiler
        "add.3 = f32[4] add": "map+reduce",
        # a fusion of map and reduce work: the joint label, in stage order
        "fusion.1 = f32[2] fusion": "map+reduce",
    }


def _entry_ops(text):
    """The entry computation's instructions, parameters and constants
    left out."""
    lines = text[text.index("\nENTRY "):].splitlines()[2:]
    entry = lines[:lines.index("}")]
    return [line for line in entry
            if not any(f" {c}(" in line for c in ("parameter", "constant"))]


def test_fused_module_ops_all_get_a_stage_and_the_scatter_is_map(
        monkeypatch):
    monkeypatch.setattr(engine, "_FUSED_CALLS", {})
    _job()
    (exe, _, _), spec = next(iter(engine._FUSED_CALLS.items()))
    assert exe is engine._fused_executable(JOB, compile_hybrid_plan(P1),
                                           _mesh(), "unicast", "xla")
    table = engine.fused_op_stages()
    ops = _entry_ops(exe.lower(spec).compile().as_text())
    assert ops
    for line in ops:
        assert table.get(tracing.op_key(line)) in engine.FUSED_STAGES, line
    scatters = [line for line in ops if "scatter-add" in line]
    assert scatters
    for line in scatters:
        assert table[tracing.op_key(line)] == "map"


def test_op_key_in_two_recorded_programs_is_left_out(monkeypatch):
    longer = np.tile(_subfiles(), 2)     # one program per argument shape
    tables = []
    for runs in ([None], [longer], [None, longer]):
        monkeypatch.setattr(engine, "_FUSED_CALLS", {})
        for subfiles in runs:
            _job(subfiles=subfiles)
        tables.append(engine.fused_op_stages())
    a, b, both = tables
    assert len(engine._FUSED_CALLS) == 2
    assert set(a) & set(b) and set(a) ^ set(b)
    assert both == {k: v for k, v in {**a, **b}.items()
                    if not (k in a and k in b)}
    # one program recorded twice is one program
    _job()
    assert engine.fused_op_stages() == both


_FOUR_DEVICES = """
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.core.params import SchemeParams
from repro.mapreduce import engine
from repro.mapreduce.jobs import histogram_job
out = {}
for name, mesh, r, multicast in [("coded", (4, 1), 2, "coded"),
                                 ("two_stage", (2, 2), 1, "unicast")]:
    engine._FUSED_CALLS.clear()
    p = SchemeParams(K=4, P=mesh[0], Q=16, N=12, r=r)
    x = np.random.default_rng(0).integers(0, 1000, (12, 64)).astype(np.int32)
    engine.run_job_distributed(histogram_job(), x, p,
                               jax.make_mesh(mesh, ("rack", "server")),
                               multicast=multicast)
    out[name] = engine.fused_op_stages()
print(json.dumps(out))
"""


def test_four_device_stages_name_the_exchanges_and_the_codec():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _FOUR_DEVICES],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr
    tables = json.loads(proc.stdout.strip().splitlines()[-1])

    def a2a(table):
        return sorted(v for k, v in table.items()
                      if k.endswith(" all-to-all"))
    coded, two_stage = tables["coded"], tables["two_stage"]
    assert a2a(coded) == ["stage1"]      # mesh (4, 1): one server a rack
    assert a2a(two_stage) == ["stage1", "stage2"]
    labels = {s for v in coded.values() for s in v.split("+")}
    assert {"map", "stage1", "encode", "decode", "reduce"} <= labels
    assert "encode" in coded.values()    # the combine, on its own
