"""Cells of ``BENCHMARK.json`` cut to sizes a CPU test holds, and a run of
one through the harness with its look for a chip skipped."""

import time

from bench.lib import runner
from bench.lib.cell import ROOT, Cell

# per configuration kind (its data maker): the sizes a test run holds
SIZES = {
    "dense_planted": dict(n_rows=3000, n_features=300, block=[1024, 128],
                          panel_rows=1024),
    # some thirty words a document: with rcv1's own seventy-six, near-equal
    # distances (whose order rounding may swap) are rare; with three they
    # are common and the Lloyd steps part ways
    "sparse_text": dict(n_rows=3000, n_features=1000, density=0.03,
                        block_rows=512, block_nse=20000, vocab=20),
}
PARAMS = {"poisson": dict(pool_rows=600, rate_per_s=200)}


def cell(name: str, seed: int = 2**40 + 3) -> Cell:
    c = Cell.load(name, seed)
    c.config.update(SIZES[c.config["data"]])
    c.params.update(PARAMS.get(c.params["traffic"], {}))
    return c


def run(c: Cell, trace: bool = False, seconds: float = 0.5,
        tmp=None) -> dict:
    out = ROOT / ".bench_out" if tmp is None else tmp
    return runner.run(c, seconds, trace, time.perf_counter(),
                      require_tpu=False, out_dir=out)

