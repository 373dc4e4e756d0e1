"""Guarded plan execution: error classification, retry, degradation ladder.

The paper's runtime (PyCOMPSs) absorbs task failures for free — a died task
is re-submitted, the data structure survives.  The jit-compiled executor has
no runtime underneath it, so the resilience has to live in the driver:
:func:`run_resilient` wraps a plan execution with

1. **classification** (:func:`classify_error`) — *transient* failures
   (device loss, UNAVAILABLE, interconnect hiccups) are worth retrying;
   *oom* (RESOURCE_EXHAUSTED in device memory) is deterministic for the
   same program but recoverable by running a cheaper program; a kernel
   that the compiler refuses (a Mosaic error, or a tile that overflows the
   kernel's VMEM) is a fault of that kernel, and like everything else is
   *deterministic* — retrying recomputes the same failure, so it raises
   immediately (unlike the seed's ``run_with_restarts``, which burned
   ``max_failures`` restarts on any exception whatsoever);

2. **retry with exponential backoff** for transients, bounded by
   ``RetryPolicy.max_retries``;

3. **a degradation ladder** for OOM: the fused jitted plan (one XLA
   program, peak-HBM heavy — every intermediate of the fused body is live
   inside one launch) degrades to per-node eager execution (each DAG node
   its own dispatch: smaller peak, more launches), then to the einsum GEMM
   backend (``REPRO_GEMM=einsum`` — no Pallas VMEM accumulator, XLA picks
   its own tiling).  Results are bit-compatible modulo float reassociation,
   so a degraded execution still satisfies the differential oracle;

4. an optional **numerical post-condition** (``guard="finite"``) — one
   fused reduction per root on the clean path, block-coordinate
   :class:`~repro.resilience.guards.NumericalDivergence` on failure.

Counters (``stats()``) record retries / degradations / recoveries so tests
and benchmarks can assert the clean path is clean (all zeros) and each
recovery path actually ran.
"""

from __future__ import annotations

import dataclasses
import re
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

from repro.core import expr as _expr
from repro.core import plan as _plan
from repro.obs import metrics as _metrics
from repro.obs import tracing as _tracing
from repro.resilience import inject as _inject
from repro.resilience.guards import NumericalDivergence, guard_finite, \
    poison_block

# ---------------------------------------------------------------------------
# Error classification
# ---------------------------------------------------------------------------

TRANSIENT = "transient"
OOM = "oom"
DETERMINISTIC = "deterministic"

# message patterns for errors that arrive as opaque runtime exceptions
# (jaxlib raises XlaRuntimeError with the gRPC status baked into the text).
# A kernel's VMEM overflow also reads RESOURCE_EXHAUSTED, but it is a compile
# error of that kernel's tiling: stepping down the ladder would only hide it
# behind the einsum rung.
_KERNEL_COMPILE_PAT = re.compile(r"memory space vmem|\bMosaic\b", re.I)
_OOM_PAT = re.compile(
    r"RESOURCE_EXHAUSTED|out of memory|\bOOM\b|allocat\w* .*exceed", re.I)
_TRANSIENT_PAT = re.compile(
    r"UNAVAILABLE|DEADLINE_EXCEEDED|ABORTED|device.{0,20}(lost|halt|reset)"
    r"|data transfer|socket closed|connection reset", re.I)

# programming / numerical errors: retrying re-raises the same thing
_DETERMINISTIC_TYPES = (
    NumericalDivergence, ArithmeticError, ValueError, TypeError,
    AssertionError, KeyError, IndexError, AttributeError, NameError,
    NotImplementedError,
)


def classify_error(exc: BaseException, default: str = DETERMINISTIC) -> str:
    """``"transient"`` | ``"oom"`` | ``"deterministic"`` for an executor
    exception.

    Injected faults classify by type; real runtime errors by status-message
    pattern; known programming/numerical error types are deterministic.
    ``default`` decides the unknown remainder: plan execution uses
    ``"deterministic"`` (an unexplained failure of a pure function will
    recur), while ``run_with_restarts`` passes ``"transient"`` (a training
    step touches hosts, disks and interconnects — the seed's
    retry-everything behaviour stays its backstop).
    """
    if isinstance(exc, _inject.OOMError):
        return OOM
    if isinstance(exc, _inject.TransientError):
        return TRANSIENT
    if isinstance(exc, (_inject.CrashError, _inject.IOLoadError)):
        return DETERMINISTIC
    if isinstance(exc, MemoryError):
        return OOM
    if isinstance(exc, _DETERMINISTIC_TYPES):
        return DETERMINISTIC
    msg = str(exc)
    if _KERNEL_COMPILE_PAT.search(msg):
        return DETERMINISTIC
    if _OOM_PAT.search(msg):
        return OOM
    if _TRANSIENT_PAT.search(msg):
        return TRANSIENT
    return default


# ---------------------------------------------------------------------------
# Policy + stats
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """How hard to try: retries/backoff for transients, ladder for OOM.

    ``retriable`` overrides :func:`classify_error` (same contract: exception
    -> class string).  ``backoff`` is the first sleep; each further retry
    multiplies by ``backoff_factor`` up to ``max_backoff`` (exponential
    backoff — hammering a recovering device makes device loss worse).
    """

    max_retries: int = 3
    backoff: float = 0.0
    backoff_factor: float = 2.0
    max_backoff: float = 2.0
    retriable: Optional[Callable[[BaseException], str]] = None
    ladder: Tuple[str, ...] = ("fused", "eager", "einsum")

    def classify(self, exc: BaseException) -> str:
        if self.retriable is not None:
            return self.retriable(exc)
        return classify_error(exc)

    def delay(self, attempt: int) -> float:
        """Sleep before retry ``attempt`` (1-based)."""
        if self.backoff <= 0.0:
            return 0.0
        return min(self.backoff * self.backoff_factor ** (attempt - 1),
                   self.max_backoff)


# registered as "resilience.*" in the obs registry; CounterGroup.inc is a
# LOCKED increment — these counters are hit from PredictServer worker
# threads, where the old dict's bare `+=` read-modify-write lost updates
_STATS = _metrics.CounterGroup(
    "resilience", ("executions", "retries", "degradations", "recoveries",
                   "guard_failures"))


def stats() -> Dict[str, int]:
    """Counters since the last :func:`reset_stats` — the resilience
    analogue of ``plan.cache_stats()``; tests assert the clean path shows
    zero retries/degradations and each chaos test shows its recovery."""
    return _STATS.as_dict()


def reset_stats() -> None:
    _STATS.reset()


# ---------------------------------------------------------------------------
# Guarded execution
# ---------------------------------------------------------------------------


def _as_plan(exprs: Sequence) -> _plan.Plan:
    if len(exprs) == 1 and isinstance(exprs[0], _plan.Plan):
        return exprs[0]
    roots = [e.expr if isinstance(e, (_expr.LazyDsArray, _expr.LazyScalar))
             else e for e in exprs]
    return _plan.Plan(roots)


def _execute_rung(p: _plan.Plan, rung: str) -> tuple:
    if rung == "fused":
        return p.execute()
    if rung == "eager":
        return p.execute_eager()
    if rung == "einsum":
        return p.execute_eager(backend="einsum")
    raise ValueError(f"unknown ladder rung {rung!r}")


def run_resilient(*exprs, policy: Optional[RetryPolicy] = None,
                  guard: Optional[str] = None):
    """Execute recorded expression(s) (or a prepared :class:`~repro.core.plan.Plan`)
    with retry + degradation + optional numerical guard.

    Single expression returns its value; several return a tuple (the
    ``compute`` / ``compute_multi`` shapes).  The clean path is one extra
    function call and a counter bump around ``Plan.execute`` — plan
    optimizer and compile caches behave exactly as under ``compute()``
    (``opt_runs == 1`` hot loops keep holding).

    ``guard="finite"`` arms the whole-plan finiteness post-condition.
    """
    if guard not in (None, "finite"):
        raise ValueError(f"unknown guard {guard!r} (want None or 'finite')")
    pol = policy or RetryPolicy()
    p = _as_plan(exprs)
    _STATS.inc("executions")
    rung_i = 0
    attempts = 0
    recovered = False
    while True:
        rung = pol.ladder[rung_i]
        try:
            # one span per ATTEMPT (failed ones carry an "error" attr), so
            # a trace shows every rung the ladder walked, not just the win
            with _tracing.span("resilience.rung", rung=rung,
                               attempt=attempts):
                out = _execute_rung(p, rung)
            break
        except Exception as exc:                         # noqa: BLE001
            kind = pol.classify(exc)
            if kind == TRANSIENT and attempts < pol.max_retries:
                attempts += 1
                _STATS.inc("retries")
                recovered = True
                d = pol.delay(attempts)
                if d > 0.0:
                    time.sleep(d)
                continue
            if kind == OOM and rung_i + 1 < len(pol.ladder):
                rung_i += 1
                attempts = 0
                _STATS.inc("degradations")
                recovered = True
                continue
            raise
    if recovered:
        _STATS.inc("recoveries")
    # post-op poison (chaos for the guards): armed specs write NaN/Inf into
    # a named block coordinate of a named root
    for spec in _inject.poison_matches("plan_result"):
        from repro.core.dsarray import DsArray
        if spec.root < len(out) and isinstance(out[spec.root], DsArray):
            out = tuple(
                poison_block(v, spec.block, spec.value) if i == spec.root
                else v for i, v in enumerate(out))
    if guard == "finite":
        try:
            guard_finite(*out)
        except NumericalDivergence:
            _STATS.inc("guard_failures")
            raise
    return out[0] if len(out) == 1 else out
