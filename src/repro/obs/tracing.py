"""Structured tracing: spans, Chrome trace-event export, summary tree.

Design center: **zero overhead when disabled**.  Tracing is off by
default; ``span(...)`` then returns one shared :data:`_NULL_SPAN`
singleton — no object allocation, no clock read, no lock, no profiler
annotation — so the plan launch hot path pays a single module-flag check
(the same discipline as ``plan._fire``'s one-dict-lookup fault hook).  The
overhead-guard test asserts this literally: a 100-launch hot loop with
tracing off leaves the span-allocation counter at exactly 0.

When enabled, each ``with span(name, **attrs):`` block does two things:

* it enters a ``jax.profiler.TraceAnnotation`` under the span's bare
  name, so the span lands on the host plane of any profiler capture taken
  meanwhile, on the same clock as the device's operations;
* it records one Chrome trace-event "complete" record (``ph: "X"`` —
  name, microsecond ``ts``/``dur``, pid/tid, ``parent``, ``args``) into a
  lock-protected buffer.  ``ts`` is read from the clock the profiler
  stamps host events with (``time.time_ns``, the system's real-time
  clock), so a :func:`trace_to` export lines up with an ``.xplane.pb`` of
  the same window.  ``parent`` is the name of the enclosing span on the
  same thread (None at the top), from which self time is computed.

Spans do not wait for the device: a span over a plan launch times the
host's dispatch of it, and the device's own time is on the profiler's
device planes.  :func:`record` adds an interval known only after the fact
(a request's wait in a queue) to the buffer alone.

Exports: :func:`trace_to` writes the events captured inside its block as
a ``{"traceEvents": [...]}`` JSON file loadable by ``chrome://tracing`` /
Perfetto; :func:`summary` renders an aggregated tree over the
dot-separated span namespace (``plan.launch``, ``serve.dispatch``...).

Imports nothing from ``repro`` — every subsystem imports this module.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

_lock = threading.Lock()
_enabled = False
_events: List[dict] = []       # finished Chrome "X" records, append-only
_span_allocs = 0               # Span objects created since last clear()
_MAX_EVENTS = 1_000_000        # hard buffer bound; beyond it, events drop
_dropped = 0


class _Local(threading.local):
    def __init__(self):
        self.stack: List[str] = []     # names of the thread's open spans


_local = _Local()


def enabled() -> bool:
    """True while spans are being recorded (the one flag hot paths check)."""
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def span_allocations() -> int:
    """Span objects allocated since the last :func:`clear` — the
    disabled-overhead guard asserts this stays 0 with tracing off."""
    return _span_allocs


def clear() -> None:
    """Drop all buffered events and zero the allocation counter."""
    global _span_allocs, _dropped
    with _lock:
        _events.clear()
        _span_allocs = 0
        _dropped = 0


def events() -> List[dict]:
    """A snapshot copy of the buffered trace events."""
    with _lock:
        return list(_events)


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return repr(v)


class _NullSpan:
    """The disabled path: one shared, stateless, allocation-free span."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NULL_SPAN = _NullSpan()


def now_ns() -> int:
    """The tracing clock in nanoseconds: the real-time clock the profiler
    stamps its host events with.  ``record`` takes its bounds in it."""
    return time.time_ns()


def _append(evt: dict) -> None:
    global _dropped
    with _lock:
        if _enabled:
            if len(_events) < _MAX_EVENTS:
                _events.append(evt)
            else:
                _dropped += 1


def _event(name: str, t0: int, t1: int, parent: Optional[str],
           args: dict) -> dict:
    evt = {
        "name": name,
        "cat": name.split(".", 1)[0],
        "ph": "X",
        "ts": t0 / 1000,               # microseconds, trace-event unit
        "dur": (t1 - t0) / 1000,
        "pid": os.getpid(),
        "tid": threading.get_ident(),
        "parent": parent,
    }
    if args:
        evt["args"] = {k: _jsonable(v) for k, v in args.items()}
    return evt


class Span:
    """One timed block -> one profiler annotation and one Chrome "X"
    event.  Only ever constructed on the enabled path; ``set(**attrs)``
    attaches late-known attributes (e.g. cache-hit status discovered
    mid-block), which go to the buffer only: the annotation keeps the bare
    name."""

    __slots__ = ("name", "args", "_t0", "_note", "_parent")

    def __init__(self, name: str, args: Optional[dict] = None):
        global _span_allocs
        _span_allocs += 1
        self.name = name
        self.args = args or {}
        self._t0 = 0
        self._note = None
        self._parent = None

    def set(self, **attrs) -> "Span":
        self.args.update(attrs)
        return self

    def __enter__(self) -> "Span":
        stack = _local.stack
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        self._note = TraceAnnotation(self.name)
        self._note.__enter__()
        self._t0 = time.time_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.time_ns()
        self._note.__exit__(exc_type, exc, tb)
        _local.stack.pop()
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        _append(_event(self.name, self._t0, t1, self._parent, self.args))
        return False


def span(name: str, **attrs):
    """``with span("plan.launch", plan=key): ...`` — records one trace
    event when tracing is enabled, returns the shared no-op singleton
    otherwise."""
    if not _enabled:
        return _NULL_SPAN
    return Span(name, attrs)


def traced(fn=None, *, name: Optional[str] = None, **static_attrs):
    """Decorator form: ``@traced`` or ``@traced(name="ingest.load")``.
    The disabled path is a flag check + direct call — no span object."""
    def deco(f):
        label = name or f"{f.__module__.rsplit('.', 1)[-1]}.{f.__qualname__}"

        @functools.wraps(f)
        def wrapper(*a, **kw):
            if not _enabled:
                return f(*a, **kw)
            with Span(label, dict(static_attrs)):
                return f(*a, **kw)
        return wrapper
    if fn is not None:                       # bare @traced
        return deco(fn)
    return deco


def record(name: str, start: int, end: int, **attrs) -> None:
    """Add one interval known only after the fact — ``start``/``end`` in
    :func:`now_ns` nanoseconds — to the buffer (never to the profiler),
    while tracing is enabled.  Its ``parent`` is the innermost span open
    on the calling thread."""
    if not _enabled:
        return
    stack = _local.stack
    _append(_event(name, start, end, stack[-1] if stack else None, attrs))


@contextlib.contextmanager
def trace_to(path: str):
    """Enable tracing for the block, then write the events captured inside
    it to ``path`` as Chrome trace-event JSON (``chrome://tracing`` /
    Perfetto load it directly).  Nesting under an already-enabled tracer
    captures the inner window without disabling the outer one."""
    was_enabled = _enabled
    with _lock:
        start = len(_events)
    enable()
    try:
        yield
    finally:
        if not was_enabled:
            disable()
        with _lock:
            captured = list(_events[start:])
        with open(path, "w") as f:
            json.dump({"traceEvents": captured, "displayTimeUnit": "ms"},
                      f, indent=1)


# ---------------------------------------------------------------------------
# Terminal summary tree
# ---------------------------------------------------------------------------


def summary(evts: Optional[List[dict]] = None) -> str:
    """Aggregate spans by their dot-separated names into a tree::

        plan                    12x     38.21ms
          launch                10x     33.90ms
          optimize               2x      4.31ms

    Parent rows aggregate their subtree (a bare ``plan`` span and the
    rollup of ``plan.*`` children both land on the ``plan`` row)."""
    if evts is None:
        evts = events()
    agg: Dict[tuple, List[float]] = {}     # name-path -> [count, total_us]
    for e in evts:
        parts = tuple(e["name"].split("."))
        dur = float(e.get("dur", 0.0))
        for i in range(1, len(parts) + 1):
            node = agg.setdefault(parts[:i], [0, 0.0])
            if i == len(parts):
                node[0] += 1
            node[1] += dur
    if not agg:
        return "(no spans recorded)"
    lines = []
    for path in sorted(agg):
        count, total_us = agg[path]
        label = "  " * (len(path) - 1) + path[-1]
        n = count if count else sum(
            agg[p][0] for p in agg if p[:len(path)] == path)
        lines.append(f"{label:<32}{n:>6}x{total_us / 1e3:>12.2f}ms")
    if _dropped:
        lines.append(f"(+{_dropped} events dropped at buffer bound)")
    return "\n".join(lines)
