"""Each cell's comparison fails its control (the reference one precision
step below the cell's, put in the program's place) and each fault the
cell's timed path can have (planted in the program), at a size a CPU test
holds.  The faults of an entry are the ``FAULTS`` of its module under
``bench/tests/faults/``."""

import json

import pytest

from bench.lib.cell import ROOT, Cell
from bench.tests import faults, tiny

CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]
ENTRIES = {name: Cell.load(name, 1).params["entry"] for name in CELLS}
CASES = [(name, f) for name in CELLS
         for f in getattr(faults.of(ENTRIES[name]), "FAULTS", [])]


@pytest.mark.parametrize("entry", sorted(set(ENTRIES.values())))
def test_every_entry_plants_a_fault(entry):
    module = faults.of(entry)
    assert module is not None, f"no bench/tests/faults/{entry}.py"
    assert module.FAULTS
    assert all(callable(getattr(module, f)) for f in module.FAULTS)


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_comparison(name):
    limits = Cell.load(name, 1).params["limits"]
    numbers = tiny.outcome(name, control=True)
    assert any(v > limits[k] for k, v in numbers.items()), numbers


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_makes_the_run_incorrect(name, fault, monkeypatch, tmp_path):
    res = tiny.outcome(name, fault=fault, tmp=tmp_path, mp=monkeypatch)
    assert res["correct"] is False, res["checks"]
