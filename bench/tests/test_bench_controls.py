"""Each cell's comparison fails its control (the reference one precision
step below the cell's, put in the program's place) and each fault the
cell's timed path can have (planted in the program), at a size a CPU test
holds."""

import json

import pytest

from bench.lib import runner
from bench.lib.cell import ROOT
from bench.tests import faults, tiny

CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]
ENTRY_FAULTS = {
    "ridge_fit": ["ridge_answer_altered", "ridge_half_batch"],
    "ridge_serve": ["serve_answer_altered", "serve_half_batch"],
    "kmeans_fit": ["kmeans_state_unchanged", "kmeans_half_batch",
                   "kmeans_answer_altered"],
}
CASES = [(name, f) for name in CELLS
         for f in ENTRY_FAULTS[tiny.cell(name).params["entry"]]]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_comparison(name):
    c = tiny.cell(name)
    numbers = runner.control(c, 0.5, require_tpu=False)
    limits = c.params["limits"]
    assert any(v > limits[k] for k, v in numbers.items()), numbers


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_makes_the_run_incorrect(name, fault, monkeypatch, tmp_path):
    getattr(faults, fault)(monkeypatch)
    res = tiny.run(tiny.cell(name), tmp=tmp_path)
    assert res["correct"] is False, res["checks"]
