"""Per-(model, geometry) AOT compilation of predict plans.

D2O's lesson (arXiv 1606.05385) is that a data-object layer pays for itself
when expensive preparation amortizes over many cheap downstream uses; here
the expensive part of a predict request is first-call XLA compilation of
its plan, and the amortization is explicit: at model-LOAD time the cache
records the estimator's predict plan on a representative zero input for
every declared geometry bucket and pushes it through
``Plan.compile_aot()`` — ``jit(body).lower().compile()`` into the shared
structural plan cache — so the FIRST real request of any warmed geometry
replays an existing executable.

Steady-state contract (asserted by ``tests/test_serve.py`` and reported in
``BENCH_serve.json``): across a request stream of warmed geometries,
``plan.cache_stats()`` shows ``opt_runs`` frozen after warmup (every
request's re-recording hits ``_OPT_CACHE``), zero new compiled-cache
misses, and ``serve.stats()["cache_hits"] == requests``.

Estimators that cannot record predict as a plan (``has_predict_plan()``
False — e.g. the forest's host-driven vote or CSVM's host decision) still
get geometry bucketing: ``warm`` runs one eager predict per bucket so
XLA's own jit caches are primed, and dispatch routes through eager
``predict`` at bucket geometry — cold-start is still hidden, there is just
no plan-level cache accounting.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from repro.core import plan as _plan
from repro.core.dsarray import DsArray
from repro.serve import stats as _stats
from repro.serve.batching import BucketSpec, GeometryBucket, \
    representative_input


class PredictCompileCache:
    """AOT-warmed predict plans for ONE estimator across its bucket set.

    The warmed executables donate no input: they sit in the shared
    structural plan cache, where a direct ``predict`` of a bucket-shaped
    array and a retry of a failed launch bind them too, and a donated leaf
    would be the caller's array.
    """

    def __init__(self, estimator, spec: BucketSpec):
        self.estimator = estimator
        self.spec = spec
        self.plan_backed = estimator.has_predict_plan()
        #: bucket -> structural key of the warmed plan (the cache-hit oracle)
        self.warmed_keys: Dict[GeometryBucket, tuple] = {}
        #: bucket -> the warmed Plan (kept for analysis linting / tests)
        self.plans: Dict[GeometryBucket, _plan.Plan] = {}

    def warm(self) -> int:
        """Record + AOT-compile the predict plan for every declared bucket
        (idempotent).  Returns the number of fresh XLA compilations — a
        steady-state re-warm returns 0."""
        compiled = 0
        for bucket in self.spec.buckets():
            x = representative_input(bucket)
            if not self.plan_backed:
                # no recordable plan: one eager predict primes the jit
                # caches inside the estimator's own predict path
                if bucket not in self.warmed_keys:
                    self.estimator.predict(x)
                    self.warmed_keys[bucket] = ()
                continue
            p = self.estimator.predict_plan(x)
            if p.compile_aot():
                compiled += 1
            self.warmed_keys[bucket] = p.key
            self.plans[bucket] = p
        return compiled

    def plan_for(self, x: DsArray,
                 bucket: GeometryBucket) -> Tuple[Optional[_plan.Plan], bool]:
        """The predict plan for a bucket-shaped batch ``x`` -> ``(plan,
        warmed)``.  ``warmed`` is True when the plan's structural key
        matches the bucket's AOT entry — the per-request cache-hit counter
        the acceptance asserts equals the request count."""
        if not self.plan_backed:
            return None, False
        p = self.estimator.predict_plan(x)
        return p, p.key == self.warmed_keys.get(bucket)

    def warmed_plans(self) -> List[_plan.Plan]:
        """The distinct warmed plans (for ``python -m repro.analysis``)."""
        seen, out = set(), []
        for p in self.plans.values():
            if p.key not in seen:
                seen.add(p.key)
                out.append(p)
        return out


def record_cache_outcome(warmed: bool, n_requests: int) -> None:
    """Account one batched plan dispatch against the serve counters."""
    _stats.bump("cache_hits" if warmed else "cache_misses", n_requests)
