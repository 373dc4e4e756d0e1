"""The benchmark's files hold together: every cell names a configuration,
an entry, a traffic driver and limits that exist, a data maker with the
sizes a CPU test holds and an entry with planted faults; every name and
unit is well formed; every per-layer metric has a reader and is reported
where the metric it moves is."""

import importlib
import json
import re

import pytest

from bench.lib.cell import ROOT, Cell, metric_reader
from bench.tests import faults

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1] == "bench/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve(name):
    cell = Cell.load(name, 1)
    assert cell.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == name)
    entry, traffic, data = cell.entry(), cell.traffic(), cell.data()
    assert callable(entry.setup) and callable(entry.check)
    assert callable(entry.control) and callable(traffic.run)
    assert callable(data.make)
    assert cell.params["limits"]
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer


@pytest.mark.parametrize("name", CELLS)
def test_cell_brings_tiny_sizes_and_faults(name):
    cell = Cell.load(name, 1)
    assert isinstance(getattr(cell.data(), "TINY", None), dict), \
        cell.config["data"]
    assert faults.of(cell.params["entry"]) is not None, cell.params["entry"]


def test_config_files_state_their_source():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert c["source"].startswith(cfg["source"].split(" ")[0])


def test_per_layer_metrics_move_a_reported_metric():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert callable(metric_reader(m["name"]).read)
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in CELLS
            assert w in moved.get("workloads", CELLS), (m["name"], w)
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_no_code_file_lists_cells():
    for path in (ROOT / "bench").rglob("*.py"):
        if "tests" in path.parts:
            continue
        text = path.read_text()
        for name in CELLS:
            assert name not in text, (path, name)


def test_modules_import_without_a_chip():
    for sub in ("entries", "traffic", "metrics", "data", "reference", "lib"):
        for path in (ROOT / "bench" / sub).glob("*.py"):
            importlib.import_module(f"bench.{sub}.{path.stem}")
