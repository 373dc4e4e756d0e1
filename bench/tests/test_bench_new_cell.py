"""A cell is added from new files alone: a configuration, a workload, a
data maker, an entry and a fault module of its own, on four chips, named
in a copy of ``BENCHMARK.json`` outside the tree.  On four CPU devices its
tiny run gives a correct result line, and its fault makes the run
incorrect; no file of the tree is touched."""

import json
import textwrap

from bench.lib.cell import ROOT
from bench.tests import tiny

NEW = {
    "bench/configs/toy.json": """
        {"name": "toy", "source": "https://example.org/toy", "data": "toy_rows",
         "n_rows": 8192, "n_cols": 512, "block": [256, 256], "reduced": []}
        """,
    "bench/workloads/toy.colsum.json": """
        {"entry": "toy_colsum", "traffic": "closed", "annotation": "fit",
         "metric": "toy_colsum_s", "limits": {"sum_err": 1e-5}}
        """,
    "bench/data/toy_rows.py": '''
        """Standard normal rows from the seed, on the host."""
        import numpy as np

        TINY = dict(n_rows=64, n_cols=32, block=[16, 16])


        def make(cell):
            c = cell.config
            return cell.rng("x").standard_normal(
                (c["n_rows"], c["n_cols"])).astype(np.float32)
        ''',
    "bench/entries/toy_colsum.py": '''
        """Column sums of rows distributed over a 2x2 mesh of the cell's
        chips, against NumPy's in float64."""
        import jax
        import numpy as np
        from jax.sharding import Mesh


        def setup(cell):
            from repro.core.dsarray import from_array
            x = cell.data().make(cell)
            mesh = Mesh(np.array(jax.devices()[:cell.chips]).reshape(2, 2),
                        ("data", "model"))
            state = {"x": x, "xd": from_array(
                x, tuple(cell.config["block"])).distribute(mesh)}
            call(state)
            return state


        def call(state):
            return np.asarray(state["xd"].sum(axis=0).collect()).ravel()


        def check(cell, state, answers):
            want = state["x"].astype(np.float64).sum(axis=0)
            return {"sum_err": max(float(np.abs(a - want).max())
                                   / float(np.abs(want).max())
                                   for a in answers)}
        ''',
    "bench/tests/faults/toy_colsum.py": '''
        """Faults under the distributed column sum."""
        from bench.tests.faults import _patch

        FAULTS = ["colsum_altered"]


        def colsum_altered(mp=None):
            """Each column sum off by one."""
            from repro.core.dsarray import DsArray

            def wrap(total):
                def bad(self, axis=None):
                    return total(self, axis) + 1.0
                return bad
            _patch(mp, DsArray, "sum", wrap)
        ''',
}


def _tree():
    return sorted((str(p), p.stat().st_mtime_ns)
                  for p in (ROOT / "bench").rglob("*")
                  if "__pycache__" not in p.parts)


def test_a_new_four_chip_cell_runs_from_new_files_alone(tmp_path):
    before = _tree()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "https://example.org/toy",
                             "file": "bench/configs/toy.json", "reduced": [],
                             "why": "distributed column sums"})
    bench["workloads"].append({"name": "toy.colsum", "config": "toy",
                               "traffic": "closed", "chips": 4,
                               "why": "a closed loop of column sums"})
    bench["end_to_end"].append({"name": "toy_colsum_s", "unit": "s",
                                "better": "lower", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["toy.colsum"]})
    bench["per_layer"].append({"name": "idle_share.toy", "unit": "%",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "toy_colsum_s",
                               "workloads": ["toy.colsum"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for rel, text in NEW.items():
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text(textwrap.dedent(text).lstrip())

    res = tiny.outcome("toy.colsum", tmp=tmp_path / "out", root=tmp_path)
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"toy_colsum_s", "setup_s"}
    bad = tiny.outcome("toy.colsum", fault="colsum_altered",
                       tmp=tmp_path / "out", root=tmp_path)
    assert bad["correct"] is False, bad["checks"]
    assert _tree() == before
