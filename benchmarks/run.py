"""Benchmark driver — one section per paper figure (+ beyond-paper tables).

Prints ``name,us_per_call,derived`` CSV and writes ``BENCH_matmul.json``
(one record per measured GEMM: op, size, us_per_call, backend, interpret)
and ``BENCH_lazy.json`` (lazy-vs-eager elementwise chains) next to the CSV
so the perf trajectories are machine-trackable across PRs.  GEMM records
with ``interpret: true`` are CPU emulations of the Pallas kernel and are
excluded from headline comparisons.  Roofline tables come from the dry-run
artifacts (see ``benchmarks/report_roofline.py``), not from here, since
they require the 512-device lowering.
"""

from __future__ import annotations

import json
import os
from pathlib import Path


def main() -> None:
    import repro
    repro.enable_compile_cache(Path(__file__).resolve().parents[1]
                               / ".jax_cache")
    from benchmarks import (bench_als, bench_estimators, bench_io,
                            bench_kmeans, bench_lazy, bench_matmul,
                            bench_serve, bench_shuffle, bench_slicing,
                            bench_sparse, bench_transpose)
    from benchmarks.common import emit

    print("name,us_per_call,derived")
    for mod in (bench_transpose, bench_als, bench_shuffle, bench_slicing,
                bench_kmeans, bench_matmul, bench_lazy, bench_sparse,
                bench_estimators, bench_serve, bench_io):
        emit(mod.run())

    out = os.environ.get("REPRO_BENCH_JSON", "BENCH_matmul.json")
    with open(out, "w") as f:
        json.dump(bench_matmul.JSON_RECORDS, f, indent=2)
    print(f"# wrote {out} ({len(bench_matmul.JSON_RECORDS)} records)")

    lazy_out = os.environ.get("REPRO_BENCH_LAZY_JSON", "BENCH_lazy.json")
    with open(lazy_out, "w") as f:
        json.dump(bench_lazy.JSON_RECORDS, f, indent=2)
    print(f"# wrote {lazy_out} ({len(bench_lazy.JSON_RECORDS)} records)")

    sparse_out = os.environ.get("REPRO_BENCH_SPARSE_JSON", "BENCH_sparse.json")
    with open(sparse_out, "w") as f:
        json.dump(bench_sparse.JSON_RECORDS, f, indent=2)
    print(f"# wrote {sparse_out} ({len(bench_sparse.JSON_RECORDS)} records)")

    est_out = os.environ.get("REPRO_BENCH_EST_JSON", "BENCH_estimators.json")
    with open(est_out, "w") as f:
        json.dump(bench_estimators.JSON_RECORDS, f, indent=2)
    print(f"# wrote {est_out} ({len(bench_estimators.JSON_RECORDS)} records)")

    serve_out = os.environ.get("REPRO_BENCH_SERVE_JSON", "BENCH_serve.json")
    with open(serve_out, "w") as f:
        json.dump(bench_serve.JSON_RECORDS, f, indent=2)
    print(f"# wrote {serve_out} ({len(bench_serve.JSON_RECORDS)} records)")

    io_out = os.environ.get("REPRO_BENCH_IO_JSON", "BENCH_io.json")
    with open(io_out, "w") as f:
        json.dump(bench_io.JSON_RECORDS, f, indent=2)
    print(f"# wrote {io_out} ({len(bench_io.JSON_RECORDS)} records)")


if __name__ == "__main__":
    main()
