"""BENCHMARK.json resolves by name to files, and keeps the contract's
shape; a new traffic mix is taken up by adding a file."""
import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from chipbench import gen, registry  # noqa: E402

BENCH = registry.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    w = registry.cell(BENCH, cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    cfg = registry.config(w["config"])
    assert cfg["name"] == w["config"]
    t = registry.traffic(w["traffic"])
    assert t["rate_turns_per_s"] > 0
    assert [c for c in BENCH["configs"] if c["name"] == w["config"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_what_its_metrics_move(cell):
    e2e = {m["name"] for m in registry.metrics_of(BENCH, cell, "end_to_end")}
    layer = registry.metrics_of(BENCH, cell, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e, (cell, m["name"])


@pytest.mark.parametrize("m", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(m):
    assert callable(registry.reader(m))


def test_names_units_and_keys():
    seen = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("chipbench/configs/")
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg) and cfg["source"] == c["source"]
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert m["name"] not in seen
            seen.add(m["name"])
            assert set(m.get("workloads", [])) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for w in BENCH["workloads"]:
        for key in ("name", "config", "traffic"):
            assert NAME.match(w[key])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs) == len(set(CELLS))


def test_a_new_traffic_file_is_taken_up_by_name(tmp_path):
    (tmp_path / "traffic").mkdir()
    mix = {"rate_turns_per_s": 500, "turns_min": 2, "turns_max": 4,
           "live_population": 100, "shift_prob": 0.3}
    (tmp_path / "traffic" / "conv-burst-test.json").write_text(
        json.dumps(mix))
    got = registry.traffic("conv-burst-test", base=tmp_path)
    assert got == mix
    s = gen.schedule(got, 1, 4.0, 256)
    assert len(s.window) == 2000 and s.turns == 4


def test_a_new_metric_file_is_taken_up_by_name(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "turns_seen.x.py").write_text(
        "def read(obs):\n    return len(obs.records)\n")

    class Obs:
        records = [1, 2, 3]
    assert registry.reader("turns_seen.x", base=tmp_path)(Obs()) == 3


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        registry.cell(BENCH, "no-such-cell")
