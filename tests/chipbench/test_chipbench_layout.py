"""BENCHMARK.json and the files it names: every cell's traffic and
configuration exist and agree, every per-layer metric has its reader,
and names and units keep to the allowed characters."""
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + \
        [m["name"] for m in _metrics()]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in _metrics():
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len(set(CELLS)) == len(CELLS)
    assert len({m["name"] for m in _metrics()}) == len(_metrics())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_agree(cell):
    from chipbench.harness import load_cell
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    c = load_cell(cell)
    assert c.config["name"] == entry["config"]
    assert (ROOT / "chipbench" / "references"
            / f"{c.config['job']}.py").is_file()
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer


def test_per_layer_metrics_have_readers_and_move_an_end_to_end_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_config_files_are_under_paths_and_distinct():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert json.loads((ROOT / c["file"]).read_text())["name"] == \
            c["name"]


@pytest.mark.parametrize("path", sorted(
    (ROOT / "chipbench" / "traffic").glob("*.json")), ids=lambda p: p.stem)
def test_traffic_files_hold_exactly_what_the_harness_reads(path):
    from chipbench.harness import TRAFFIC_KEYS
    assert set(json.loads(path.read_text())) == TRAFFIC_KEYS


def test_traffic_with_a_key_the_harness_does_not_read_is_refused(
        monkeypatch):
    from chipbench import harness
    real = harness._load_json

    def with_loop(path):
        d = real(path)
        if "traffic" in pathlib.Path(path).parts:
            d = dict(d, loop={"kind": "closed", "clients": 2})
        return d
    monkeypatch.setattr(harness, "_load_json", with_loop)
    with pytest.raises(SystemExit, match="loop"):
        harness.load_cell("wordcount.1chip")
