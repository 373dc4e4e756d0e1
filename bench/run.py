"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared with the plain reference beside its
limit, which are also the last lines of standard error.  Where JAX finds
no TPU, or fewer chips than the cell asks for, the run exits non-zero and
prints no result.  A cell on four chips is rehearsed on the CPU by the
tests, in a child process with four host devices (``bench/tests/tiny.py``).
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime's logs, which otherwise go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_out" / "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import repro
    from bench.lib import runner
    from bench.lib.cell import Cell

    cell = Cell.load(args.workload, args.seed, ROOT)
    try:
        runner.devices(cell.chips)
    except runner.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    runner.log(f"compile cache: "
               f"{repro.enable_compile_cache(ROOT / '.jax_cache')}")
    result = runner.run(cell, args.seconds, bool(args.trace), T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
