"""Run one cell once: set-up, the measured window, the comparison with the
plain reference, and the result line.

The order is fixed by what each step must not disturb.  Set-up (data, the
program's objects, one warm call of every shape the window uses) runs
first and ends the set-up time.  The traffic driver then measures for the
given seconds, with the profiler on only in a traced run.  The device's
memory peak is read next, then the entry frees the program's objects and
compares what the window produced with its plain reference.
"""

from __future__ import annotations

import dataclasses
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from bench.lib import trace as tr
from bench.lib.cell import ROOT, Cell, metric_reader
from bench.lib.peaks import peaks


_T0 = time.perf_counter()


class NoDevice(Exception):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


class _Compiles:
    """XLA backend compilations since the process started."""

    count = 0
    seconds = 0.0
    _registered = False

    @classmethod
    def register(cls) -> None:
        if not cls._registered:
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls._registered = True

    @classmethod
    def _on(cls, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            cls.count += 1
            cls.seconds += secs


@dataclasses.dataclass
class WindowResult:
    """What a traffic driver hands back."""

    attempted: int
    failed: int
    metrics: Dict[str, float]       # the cell's end-to-end metrics but setup_s
    answers: list                   # what the entry's ``check`` compares
    calls: int                      # completed calls into the program
    notes: Dict[str, object] = dataclasses.field(default_factory=dict)


class Window:
    """The measured window.  In a traced run it starts the profiler and,
    where a per-layer metric reads the program's spans, its tracing; the
    ``window`` annotation marks it in the trace."""

    def __init__(self, trace: bool, trace_dir: Path, spans: bool):
        self.trace, self.trace_dir, self.spans = trace, trace_dir, spans
        self.t0 = self.t1 = self.trace_stop_s = 0.0
        self.counters: Dict[str, object] = {}
        self.span_events: List[dict] = []

    def __enter__(self) -> "Window":
        from repro import obs
        self._c0 = obs.snapshot()
        if self.trace:
            if self.spans:
                obs.clear()
                obs.enable()
            # the host's annotations and the device's ops, without the
            # Python tracer, which records every Python call the window
            # makes and slows the host path it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(tr.WINDOW)
        self._mark.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        from repro import obs
        self.t1 = time.perf_counter()
        self._mark.__exit__(*exc)
        if self.trace:
            jax.profiler.stop_trace()
            self.trace_stop_s = time.perf_counter() - self.t1
            if self.spans:
                obs.disable()
                self.span_events = obs.events()
        c1 = obs.snapshot()
        self.counters = {k: v - self._c0.get(k, 0) for k, v in c1.items()
                         if isinstance(v, (int, float))}

    @property
    def elapsed(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""

    cell: Cell
    state: dict                     # the entry's, before the comparison
    reduction: Optional[tr.Reduction]
    counters: Dict[str, float]
    spans: List[dict]
    calls: int
    device_kind: str


def devices(chips: int, require_tpu: bool = True):
    """The devices the cell runs on; raises :class:`NoDevice` where JAX
    finds no TPU or fewer than ``chips``."""
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX sees {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def hold_precision(cell: Cell) -> None:
    """Run the program at the matmul precision the cell's configuration
    states, where it states one."""
    precision = cell.config.get("matmul_precision")
    if precision:
        jax.config.update("jax_default_matmul_precision", precision)


def memory_peak(devs) -> int:
    """The peak bytes in use on the fullest of ``devs`` (0 where the
    backend does not say)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:.1f} s] {msg}", file=sys.stderr,
          flush=True)


def run(cell: Cell, seconds: float, trace: bool, t0: float,
        require_tpu: bool = True, out_dir: Path = ROOT / ".bench_out"
        ) -> dict:
    """Run ``cell`` once and return its result line as a dict."""
    devs = devices(cell.chips, require_tpu)
    kind = devs[0].device_kind
    if require_tpu:
        peaks(kind)
    used = devs[: cell.chips]
    _Compiles.register()
    hold_precision(cell)
    entry, traffic = cell.entry(), cell.traffic()

    state = entry.setup(cell)
    from repro.core import plan
    misses0, compiles0 = plan.cache_stats()["misses"], _Compiles.count
    trace_dir = out_dir / "trace" / f"{cell.name}-{cell.seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    spans = any(m["source"] == "program_span" for m in cell.per_layer)
    win = Window(trace, trace_dir, spans)
    setup_s = time.perf_counter() - t0
    out = traffic.run(cell, entry, state, seconds, win)
    log(f"window: {win.elapsed!r} s, {out.calls} calls, "
        f"{out.attempted} attempted, {out.failed} failed")
    log(f"compiles inside the window: {_Compiles.count - compiles0} "
        f"(plan.misses grew by {plan.cache_stats()['misses'] - misses0})")
    for k, v in out.notes.items():
        log(f"{k}: {v!r}")
    mem = memory_peak(used)

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": False, "attempted": out.attempted,
              "failed": out.failed, "metrics": {}, "device": device}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        t_read = time.perf_counter()
        red = tr.load(str(trace_dir))
        log(f"trace: stopped in {win.trace_stop_s!r} s, read in "
            f"{time.perf_counter() - t_read!r} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_ns / 1e9
        ctx = Context(cell, state, red, win.counters, win.span_events,
                      out.calls, kind)
        for m in cell.per_layer:
            value = metric_reader(m["name"]).read(ctx, m)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red.top_ops(),
                               "idle_gaps": red.idle_gaps()}
    else:
        values = dict(out.metrics, setup_s=setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                            "unit": units[m["name"]]}

    numbers = entry.check(cell, state, out.answers)
    limits = cell.params["limits"]
    checks = {}
    for name, value in numbers.items():
        limit = float(limits[name])
        checks[name] = {"value": float(value), "limit": limit}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    result["correct"] = bool(ok and out.failed == 0 and checks)
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    result["checks"] = checks
    return result


def control(cell: Cell, seconds: float, require_tpu: bool = True) -> dict:
    """The control: a window at the cell's own load, then the reference at
    the precision one step below the configuration's in the program's
    place; the numbers it reads, which a sound comparison has to fail.
    The benchmark's own runs never call this."""
    devices(cell.chips, require_tpu)
    hold_precision(cell)
    entry = cell.entry()
    state = entry.setup(cell)
    out = cell.traffic().run(cell, entry, state, seconds,
                             Window(False, ROOT, False))
    return entry.check(cell, state, entry.control(cell, state, out.answers))
