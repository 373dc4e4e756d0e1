"""Cells of ``BENCHMARK.json`` cut to sizes a CPU test holds, and a run of
one through the harness with its look for a chip skipped.

The sizes are found through the modules the cell names: its data maker's
``TINY`` and, where its traffic driver has one, the driver's
``TINY_PARAMS``.  A cell on one chip runs in the test's own process.  A
cell on more chips, or one of another ``BENCHMARK.json`` than the tree's,
runs in a child process in which JAX finds as many CPU devices as the cell
asks for chips; the child finds modules under that file's ``bench/`` as
well as the tree's.
"""

import importlib
import json
import os
import subprocess
import sys
import time
from pathlib import Path

from bench.lib import runner
from bench.lib.cell import ROOT, Cell
from bench.tests import faults

SEED = 2**40 + 3
# the packages whose modules a cell names
PACKAGES = ("entries", "data", "traffic", "metrics", "reference",
            "tests.faults")


def cell(name: str, seed: int = SEED, root: Path = ROOT) -> Cell:
    c = Cell.load(name, seed, root)
    data = c.data()
    if not hasattr(data, "TINY"):
        raise LookupError(f"{data.__name__} has no TINY: the sizes of its "
                          f"configurations that a CPU test holds")
    c.config.update(data.TINY)
    c.params.update(getattr(c.traffic(), "TINY_PARAMS", {}))
    return c


def run(c: Cell, trace: bool = False, seconds: float = 0.5,
        tmp=None) -> dict:
    out = ROOT / ".bench_out" if tmp is None else tmp
    return runner.run(c, seconds, trace, time.perf_counter(),
                      require_tpu=False, out_dir=out)


def outcome(name: str, control: bool = False, fault=None,
            trace: bool = False, tmp=None, root: Path = ROOT,
            mp=None) -> dict:
    """The result line of a tiny run of the cell ``name``, or with
    ``control`` the numbers its control reads.  ``fault`` names a fault of
    the cell's entry (``bench/tests/faults/<entry>.py``), planted for the
    run: with ``mp`` (a ``monkeypatch``) in this process, for good in a
    child."""
    args = dict(name=name, control=control, fault=fault, trace=trace,
                tmp=None if tmp is None else str(tmp), root=str(root))
    chips = Cell.load(name, SEED, root).chips
    if chips == 1 and Path(root) == ROOT:
        return _outcome(mp=mp, **args)
    return _in_child(chips, args)


def _outcome(name, control, fault, trace, tmp, root, mp=None) -> dict:
    c = cell(name, root=Path(root))
    if fault:
        faults.plant(c.params["entry"], fault, mp)
    if control:
        return runner.control(c, 0.5, require_tpu=False)
    return run(c, trace, tmp=None if tmp is None else Path(tmp))


def _in_child(chips: int, args: dict) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={chips}").strip()
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; from bench.tests import tiny; tiny.child(sys.argv[1])",
         json.dumps(args)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode:
        raise RuntimeError(f"the child run of {args['name']} exited "
                           f"{proc.returncode}:\n{proc.stderr[-6000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def child(arg: str) -> None:
    """The child's side of :func:`outcome`: the last line of standard
    output is what the run gave, as JSON."""
    args = json.loads(arg)
    root = Path(args["root"])
    if root != ROOT:
        for sub in PACKAGES:
            extra = root / "bench" / sub.replace(".", "/")
            if extra.is_dir():
                importlib.import_module(f"bench.{sub}").__path__.append(
                    str(extra))
    print(json.dumps(_outcome(**args)), flush=True)
