"""Charge the device's idle time to what the host was doing meanwhile.

Every idle instant of the fullest device goes to the innermost of the
program's own spans (``repro.obs`` tracing) active at that instant,
meaning the latest started of the active spans on any thread; where none
is active, to the harness's annotation (``fit``, ``submit``, ``wait``);
where neither is, to ``host idle``.  The parts sum to the device's idle
share.

The program's spans come from its trace buffer (the ``spans`` of a metric's
context), stamped by the real-time clock the profiler stamps its host
events with; the reduction keeps the profile's times from its own start.
One offset puts the spans on the reduction's clock.  Each harness
annotation other than ``wait`` holds one call into the program, and the
window's first program span opens inside the first of them: that pair
fixes the offset, which takes the two as opening together, so the spans
land late by the host time between them (whatever the call does before its
first span).  Spans of a program whose buffer is on another clock (events
without ``parent``) are not read.
"""

from __future__ import annotations

import bisect
import heapq
from typing import Dict, List, Optional, Sequence

from bench.lib import trace as tr

# the program's span namespaces, as ``repro.obs`` names them
PREFIXES = ("fit.", "plan.", "serve.", "resilience.", "ingest.")
# intervals the program records after the fact (``obs.record``): a
# request's wait, in which the host is not busy on its behalf
WAITS = ("serve.queue",)
HOST_IDLE = "host idle"
_NOT_A_CALL = ("wait",)


class _Innermost:
    """The latest-started of ``events`` active at a time that only grows
    from one query to the next."""

    def __init__(self, events: Sequence[tr.Event]):
        self._events = sorted((s, e, n) for n, s, e in events if e > s)
        self._next = 0
        self._heap: list = []          # (-start, end, name) of started ones

    def at(self, t: int) -> Optional[str]:
        while (self._next < len(self._events)
               and self._events[self._next][0] <= t):
            s, e, n = self._events[self._next]
            heapq.heappush(self._heap, (-s, e, n))
            self._next += 1
        while self._heap and self._heap[0][1] <= t:
            heapq.heappop(self._heap)
        return self._heap[0][2] if self._heap else None


def split(intervals: Sequence[tr.Interval], spans: Sequence[tr.Event],
          annotations: Sequence[tr.Event]) -> List[Dict[str, int]]:
    """Nanoseconds by label of each of the sorted, disjoint ``intervals``."""
    cuts = sorted({t for _, s, e in list(spans) + list(annotations)
                   for t in (s, e)})
    inner, outer = _Innermost(spans), _Innermost(annotations)
    out = []
    for a, b in intervals:
        parts: Dict[str, int] = {}
        i, t = bisect.bisect_right(cuts, a), a
        while t < b:
            q = cuts[i] if i < len(cuts) and cuts[i] < b else b
            label = inner.at(t) or outer.at(t) or HOST_IDLE
            parts[label] = parts.get(label, 0) + q - t
            t, i = q, i + 1
        out.append(parts)
    return out


def _idle(red: tr.Reduction) -> List[tr.Interval]:
    lo, hi = red.window
    return tr.gaps(red.fullest().busy, lo, hi)


def idle_by_label(red: tr.Reduction,
                  spans: Sequence[tr.Event]) -> Dict[str, float]:
    """The fullest device's idle time by label, as shares of the window;
    they sum to ``red.idle_share()``."""
    tot: Dict[str, int] = {}
    for parts in split(_idle(red), spans, red.host):
        for label, ns in parts.items():
            tot[label] = tot.get(label, 0) + ns
    return {label: ns / red.window_ns for label, ns in tot.items()}


def idle_gaps(red: tr.Reduction, spans: Sequence[tr.Event],
              n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps of the fullest device, each labelled by
    the span or annotation holding most of it (``host idle`` where none
    holds any), as ``Reduction.idle_gaps`` labels them by annotation."""
    longest = sorted(_idle(red), key=lambda g: g[0] - g[1])[:n]
    in_order = sorted(longest)
    parts = dict(zip(in_order, split(in_order, spans, red.host)))
    out = []
    for g in longest:
        held = {k: v for k, v in parts[g].items() if k != HOST_IDLE}
        label = max(held, key=held.get) if held else HOST_IDLE
        out.append([label, (g[1] - g[0]) / 1e9])
    return out


def program_spans(ctx) -> Optional[List[tr.Event]]:
    """The program's spans of the window on the reduction's clock, in
    nanoseconds; None where the run gave none to read."""
    red = ctx.reduction
    spans = [e for e in ctx.spans if e["name"].startswith(PREFIXES)
             and e["name"] not in WAITS and "parent" in e]
    calls = [s for name, s, _ in red.host
             if name not in _NOT_A_CALL] if red is not None else []
    if not spans or not calls:
        return None
    # differences of the microsecond stamps are exact; the stamps
    # themselves would lose a few hundred ns in a product
    first, t0 = min(e["ts"] for e in spans), min(calls)
    out = []
    for e in spans:
        start = t0 + round((e["ts"] - first) * 1e3)
        out.append((e["name"], start, start + round(e["dur"] * 1e3)))
    return out


def idle_percent(ctx, prefixes: Sequence[str]) -> Optional[float]:
    """% of the window the fullest device is idle while the innermost
    program span is one of ``prefixes``; None where nothing was read."""
    spans = program_spans(ctx)
    if spans is None or not ctx.reduction.fullest().ops:
        return None
    shares = idle_by_label(ctx.reduction, spans)
    return 100.0 * sum(v for k, v in shares.items()
                       if k.startswith(tuple(prefixes)))
