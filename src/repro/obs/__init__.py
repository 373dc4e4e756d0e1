"""``repro.obs`` — unified telemetry: tracing, metrics, profiling.

One import surface over three concerns:

* **tracing** (:mod:`repro.obs.tracing`) — ``span``/``traced``,
  off-by-default and allocation-free while off.  While on, each span is
  also a ``jax.profiler.TraceAnnotation`` under its bare name, so it lands
  on the host plane of a profiler capture on the device trace's clock, and
  a buffered event carrying its enclosing span (``parent``); ``record``
  buffers an interval known only after the fact.  ``trace_to(path)``
  exports Chrome trace-event JSON, ``summary()`` renders the aggregated
  tree.
* **metrics** (:mod:`repro.obs.metrics`) — the process-wide
  :data:`registry` of Counter/Gauge/Histogram objects that
  ``plan.cache_stats()``, ``resilience.stats()`` and ``serve.stats()``
  are now views over; ``snapshot()``/``reset_all()`` replace hand-resetting
  three modules.
* **profiling** (:mod:`repro.obs.profiler`) — ``profile(plan)`` pairs each
  plan node's measured wall time and bytes against the ``costmodel`` laws
  (the data behind the ``costmodel-drift`` analysis rule).

``tracing`` and ``metrics`` import nothing from ``repro`` (everything
imports *them*); the profiler pulls in ``core``/``analysis`` machinery, so
it is loaded lazily on first :func:`profile` call.
"""

from __future__ import annotations

from repro.obs.metrics import (Counter, CounterGroup, Gauge, Histogram,
                               MetricsRegistry, registry)
from repro.obs.tracing import (Span, clear, disable, enable, enabled,
                               events, now_ns, record, span,
                               span_allocations, summary, trace_to, traced)
from repro.obs import tracing as _tracing


def snapshot(prefix=None):
    """Flat ``{dotted_name: value}`` over every registered metric — the
    one call benchmarks embed so perf numbers carry their cache/retry
    discipline."""
    return registry.snapshot(prefix)


def reset_all() -> None:
    """Zero every metric and drop the trace buffer (counters only — plan
    compiled caches are storage, not telemetry, and are left alone)."""
    registry.reset_all()
    _tracing.clear()


def profile(target, **kwargs):
    """Predicted-vs-measured cost report for a plan (or anything coercible
    to one).  See :func:`repro.obs.profiler.profile`."""
    from repro.obs.profiler import profile as _profile
    return _profile(target, **kwargs)


__all__ = [
    "Counter", "CounterGroup", "Gauge", "Histogram", "MetricsRegistry",
    "Span", "clear", "disable", "enable", "enabled", "events", "now_ns",
    "profile", "record", "registry", "reset_all", "snapshot", "span",
    "span_allocations", "summary", "trace_to", "traced",
]
