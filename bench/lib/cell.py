"""One cell of ``BENCHMARK.json``: its configuration, its workload file, its
metrics and the seed streams everything random is drawn from.

Nothing here lists cells, configurations or metrics.  A cell is found by
its name in ``BENCHMARK.json``; its workload file is
``bench/workloads/<cell>.json``; the modules it names are found by name:
``bench/entries/<entry>.py`` (the call into the program and the comparison
with the plain reference), ``bench/traffic/<traffic>.py`` (the driver of
the measured window) and, for a per-layer metric, ``bench/metrics/<base>.py``
where ``<base>`` is the metric's name up to its first dot, so
``idle_share.fit`` and ``idle_share.serve`` share one reader.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import zlib
from pathlib import Path
from typing import List

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    params: dict
    seed: int
    end_to_end: List[dict] = dataclasses.field(default_factory=list)
    per_layer: List[dict] = dataclasses.field(default_factory=list)

    @classmethod
    def load(cls, name: str, seed: int, root: Path = ROOT) -> "Cell":
        """The cell ``name`` of ``BENCHMARK.json``."""
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(have {', '.join(sorted(cells))})")
        w = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        config = json.loads((root / configs[w["config"]]["file"]).read_text())
        params = json.loads(
            (root / "bench" / "workloads" / f"{name}.json").read_text())
        e2e = [m for m in bench["end_to_end"]
               if name in m.get("workloads", [name])]
        reported = {m["name"] for m in e2e}
        layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in
                                  reported else [])]
        return cls(name=name, chips=int(w["chips"]), config=config,
                   params=params, seed=int(seed), end_to_end=e2e,
                   per_layer=layer)

    # -- seeds -------------------------------------------------------------
    def _seq(self, stream: str) -> np.random.SeedSequence:
        # any whole seed (the driver's exceed 32 bits), one stream per label
        return np.random.SeedSequence(
            [self.seed % 2**64, zlib.crc32(stream.encode())])

    def rng(self, stream: str) -> np.random.Generator:
        """A NumPy generator for one named stream of this seed."""
        return np.random.default_rng(self._seq(stream))

    def key(self, stream: str):
        """A raw threefry JAX key for one named stream of this seed."""
        import jax.numpy as jnp
        return jnp.asarray(self._seq(stream).generate_state(2, np.uint32))

    def int_seed(self, stream: str) -> int:
        """A 31-bit integer for a program parameter that takes a seed."""
        return int(self._seq(stream).generate_state(1, np.uint32)[0] >> 1)

    # -- modules found by name ----------------------------------------------
    def entry(self):
        return importlib.import_module(f"bench.entries.{self.params['entry']}")

    def traffic(self):
        return importlib.import_module(
            f"bench.traffic.{self.params['traffic']}")

    def data(self):
        return importlib.import_module(f"bench.data.{self.config['data']}")


def metric_reader(name: str):
    """The reader module of a per-layer metric (see the module docstring)."""
    return importlib.import_module(f"bench.metrics.{name.split('.', 1)[0]}")

