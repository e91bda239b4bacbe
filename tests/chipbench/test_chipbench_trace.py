"""The benchmark's trace reduction (``chipbench/trace.py``) and the
per-layer readers, on synthetic intervals and on a small trace recorded on
a TPU v5e: four chips running a traced window of three jobs of a wide
histogram (N = 96 subfiles of 2^18 tokens, Q = 1024, d = 2048) on mesh
(4, 1) with r = 2 and the coded multicast through the Pallas
``coded_combine`` kernels, kept by ``chipbench/run.py --seconds 1 --trace 1
--keep-trace DIR`` (``wide_hist_coded_4chip.xplane.pb``).

Reading the recorded trace needs JAX's ``ProfileData`` only: no TPU
library is loaded."""
import pathlib
import types

import pytest

from chipbench import trace

HERE = pathlib.Path(__file__).resolve().parent
FIXTURE = HERE / "wide_hist_coded_4chip.xplane.pb"


def test_interval_algebra():
    xs = trace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert xs == [(0, 3), (5, 9)]
    assert trace.intersect(xs, [(2, 6), (8, 20)]) == [(2, 3), (5, 6),
                                                      (8, 9)]
    assert trace.complement(xs, [(-1, 4), (4, 10)]) == [(-1, 0), (3, 4),
                                                        (4, 5), (9, 10)]
    assert trace.length(xs) == 7
    windows = [(0, 10), (20, 30)]
    assert trace.overlap(5, 25, windows, [0, 20]) == 10
    assert trace.overlap(11, 19, windows, [0, 20]) == 0


def test_host_activities_split_jobs_by_engine_spans():
    span = lambda phase, a, b: types.SimpleNamespace(phase=phase, ts=a,
                                                     dur=b - a)
    acts = trace.host_activities(
        [(0.0, 10.0)], [span("pack", 1.0, 4.0),
                        span("map_shuffle_reduce", 4.0, 8.0)])
    assert acts == {"pack": [(1.0, 4.0)], "map_shuffle_reduce": [(4.0, 8.0)],
                    "call_entry": [(0.0, 1.0)], "after_spans": [(8.0, 10.0)]}


def test_op_label_names_instruction_opcode_and_kind():
    enc = ('%coded_encode.1 = f32[16384,2048]{1,0:T(8,128)} custom-call('
           'f32[2,16384,2048]{2,1,0:T(8,128)} %pad, f32[2]{0:T(128)} %c), '
           'custom_call_target="tpu_custom_call"')
    assert trace.op_label(enc) == "%coded_encode.1 custom-call"
    fusion = ('%fusion.4 = f32[1024,2048]{1,0} fusion(s32[48,262144]{1,0} '
              '%p), kind=kCustom, calls=%fused_computation.4')
    assert trace.op_label(fusion) == "%fusion.4 fusion kCustom"


def _fixture_summary():
    # one window from the sync mark on covers the whole traced run
    return trace.summarize(str(FIXTURE), 0.0, [(0.0, 1e3)], [], 4)


def _covered_ns(ops):
    """Busy time by a sweep over start/end events, apart from union()."""
    edges = sorted([(a, 1) for _, a, _ in ops] + [(b, -1) for _, _, b in ops])
    depth, since, busy = 0, None, 0.0
    for t, step in edges:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_fixture_busy_union_per_chip():
    sync_ns, chips = trace.read_xplane(str(FIXTURE))
    assert [c.chip for c in chips] == [0, 1, 2, 3]
    s = _fixture_summary()
    assert s.busy_ns == [357993679.0, 357979639.0, 357968453.0,
                         357973156.0]
    for chip, busy in zip(chips, s.busy_ns):
        after_sync = [(n, a, b) for n, a, b in chip.ops if a >= sync_ns]
        assert busy == pytest.approx(_covered_ns(after_sync), abs=1.0)
    assert s.busy_s == pytest.approx(0.357978732, rel=1e-9)


def test_fixture_all_to_all_sum():
    s = _fixture_summary()
    ops = s.ops(lambda text: " all-to-all(" in text)
    assert [(trace.op_label(t), n) for t, _, n in ops] == [
        ("%all_to_all.3 all-to-all", 12.0)]      # 3 jobs x 4 chips
    assert ops[0][1] == pytest.approx(0.018537902, rel=1e-9)


def test_fixture_codec_kernel_events():
    s = _fixture_summary()
    calls = s.ops(lambda text: text.startswith(("%coded_encode",
                                                "%coded_decode"))
                  and " custom-call(" in text)
    found = sorted((trace.op_label(t), n, round(sec, 9))
                   for t, sec, n in calls)
    assert found == [("%coded_decode.1 custom-call", 12.0, 0.007273246),
                     ("%coded_encode.1 custom-call", 12.0, 0.007261157)]


def test_fixture_breakdown_per_chip():
    s = _fixture_summary()
    b = s.breakdown()
    assert len(b["device_ops"]) == 10
    assert b["device_ops"][:2] == [
        ("%fusion.4 fusion kCustom", pytest.approx(0.32950663675,
                                                   rel=1e-9)),
        ("%all_to_all.3 all-to-all", pytest.approx(0.0046344755,
                                                   rel=1e-9))]
    # no engine spans: the idle time inside the one window is all before
    # a first span
    assert [k for k, _ in b["idle_gaps"]] == ["call_entry"]
    assert b["idle_gaps"][0][1] == pytest.approx(1e3 - s.busy_s, rel=1e-9)


METRICS = sorted(p.stem for p in (HERE.parents[1] / "chipbench"
                                  / "metrics").glob("*.py"))


@pytest.mark.parametrize("name", METRICS)
def test_reader_with_nothing_to_read_returns_nothing(name):
    from chipbench.harness import load_module
    reader = load_module(str(HERE.parents[1] / "chipbench" / "metrics"
                             / f"{name}.py"), f"reader_{name}")
    empty = trace.Summary(0.0, [0.0], [{}], {})
    for t in (None, empty):
        w = types.SimpleNamespace(trace=t, device_kind="TPU v5 lite",
                                  jobs=0, spans={})
        assert reader.read(w) is None


def test_span_and_idle_readers_per_job():
    from chipbench.harness import load_module
    path = HERE.parents[1] / "chipbench" / "metrics"
    s = trace.Summary(4e9, [1e9, 3e9], [{}, {}], {})
    w = types.SimpleNamespace(trace=s, device_kind="TPU v5 lite", jobs=4,
                              spans={"pack": 0.4, "map_shuffle_reduce": 2.0})
    read = {n: load_module(str(path / f"{n}.py"), f"r_{n}").read(w)
            for n in ("pack_span_ms", "program_span_ms", "device_idle_pct")}
    assert read == pytest.approx({"pack_span_ms": 100.0,
                                  "program_span_ms": 500.0,
                                  "device_idle_pct": 50.0})
