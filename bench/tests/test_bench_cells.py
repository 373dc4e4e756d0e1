"""Each cell, cut to a size a CPU test holds, runs through the harness's
own entry (its look for a chip skipped) and gives the fields of a result
line, with the profiler off and on: a cell on one chip in the test's
process, one on more in a child process with as many CPU devices
(``tiny.outcome``).  And ``bench/run.py`` itself refuses a machine without
a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.lib.cell import ROOT, Cell
from bench.tests import tiny

CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _assert_line(res, cell, trace):
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    dev = res["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= cell.chips
    assert "memory_peak_bytes" in dev
    if trace:
        want = {m["name"] for m in cell.per_layer}
        assert set(res["metrics"]) <= want
        assert dev["busy_s"] > 0 and dev["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(res["breakdown"]["device_ops"]) <= 10
    else:
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert all(v["value"] > 0 for v in res["metrics"].values())
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("name", CELLS)
def test_cell_gives_a_result_line(name, trace, tmp_path):
    res = tiny.outcome(name, trace=trace, tmp=tmp_path)
    _assert_line(res, Cell.load(name, tiny.SEED), trace)


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_py_refuses_a_machine_without_a_tpu():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_py_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
