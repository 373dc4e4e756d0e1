"""Serving suite for repro.serve: exactness, cache discipline, chaos.

* micro-batch padding exactness: served predictions bit-identical to the
  direct ``estimator.predict`` on every bucket boundary — exact fit,
  one-row tail, ragged last block — dense AND bcoo
* steady-state plan-cache discipline: after warm, a request stream adds
  ZERO plan-cache misses / opt runs / AOT compiles, and the serve
  cache-hit counter equals the request count
* degradation ladder under injected ``serve_dispatch`` faults: transient
  retry, batch shed -> unbatched recovery, plan-level OOM absorbed by
  run_resilient, per-request failure isolation
* registry: versioned save_model/load round-trips, device pinning,
  eager-fallback serving for estimators without a recordable plan
* server mechanics: oversized/overdense fallbacks, payload validation,
  threaded serve_forever smoke
"""

import os
import tempfile
import threading

import jax.numpy as jnp
import numpy as np
import pytest

import repro.serve as serve
from repro.core import plan as plan_mod
from repro.core import sparse as sparse_mod
from repro.core.dsarray import from_array
from repro.estimators import LinearRegression, RandomForestClassifier, Ridge
from repro.resilience import FaultSpec, RetryPolicy, inject
from repro.serve.batching import (BucketSpec, GeometryBucket, assemble,
                                  normalize_payload, split_rows)

pytestmark = pytest.mark.serve

SEED = 20260808
N_FEATURES = 12

try:
    import scipy.sparse as sp
    HAVE_SCIPY = True
except ImportError:                                    # pragma: no cover
    HAVE_SCIPY = False

needs_scipy = pytest.mark.skipif(not HAVE_SCIPY, reason="scipy not installed")


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


def _fit_ridge(seed=SEED, n=256, m=N_FEATURES, alpha=0.1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, m)).astype(np.float32)
    w = rng.normal(size=(m,)).astype(np.float32)
    y = (X @ w + 0.25).reshape(-1, 1).astype(np.float32)
    est = Ridge(alpha=alpha)
    est.fit(from_array(jnp.asarray(X), (64, m)),
            from_array(jnp.asarray(y), (64, 1)))
    return est


@pytest.fixture(scope="module")
def ridge():
    return _fit_ridge()


# counter hygiene is the session-wide autouse obs.reset_all() fixture in
# conftest.py — no per-module reset needed


def _registry(est, **kw):
    # a 1-row bucket (as in the module default) keeps lone requests at
    # their natural (1, m) geometry — see the exactness note in batching
    kw.setdefault("batch_sizes", (1, 4, 16))
    kw.setdefault("block_rows", 4)
    reg = serve.ModelRegistry()
    reg.register("m", est, **kw)
    return reg


def _rows(n, seed=1, m=N_FEATURES):
    return np.random.default_rng(seed).normal(size=(n, m)).astype(np.float32)


def _sparse_rows(n, seed=1, m=N_FEATURES, density=0.3):
    return sp.random(n, m, density=density, format="csr",
                     random_state=np.random.default_rng(seed),
                     dtype=np.float32)


def _direct_dense(est, rows):
    return np.asarray(est.predict(rows).collect())


def _direct_sparse(est, mat):
    x = sparse_mod.from_scipy(mat, (mat.shape[0], mat.shape[1]))
    return np.asarray(est.predict(x).collect())


# ---------------------------------------------------------------------------
# micro-batch padding exactness at every bucket boundary
# ---------------------------------------------------------------------------

# with buckets (4, 16) and block_rows=4: exact smallest fit, smallest+1
# (pad 11 into the big bucket), exact largest fit, one-row tail, and a
# ragged last block (13 = 3 full blocks + 1 row)
BOUNDARY_TOTALS = [4, 5, 16, 15, 13, 1]


@pytest.mark.parametrize("total", BOUNDARY_TOTALS)
def test_dense_served_equals_direct(ridge, total):
    reg = _registry(ridge)
    srv = serve.PredictServer(reg)
    rows = _rows(total, seed=total)
    # split the batch over several requests so concat+pad is exercised
    sizes = [1] * total if total <= 2 else [2, total - 3, 1]
    futs, off = [], 0
    for s in sizes:
        futs.append(srv.submit("m", rows[off:off + s]))
        off += s
    assert srv.pump() == len(sizes)
    got = np.concatenate([f.result() for f in futs], axis=0)
    direct = _direct_dense(ridge, rows)
    assert got.shape == (total, 1)
    assert np.array_equal(got, direct)


@needs_scipy
@pytest.mark.parametrize("total", BOUNDARY_TOTALS)
def test_bcoo_served_equals_direct(ridge, total):
    reg = _registry(ridge, formats=("dense", "bcoo"), nse=4 * N_FEATURES)
    srv = serve.PredictServer(reg)
    mat = _sparse_rows(total, seed=total)
    sizes = [1] * total if total <= 2 else [2, total - 3, 1]
    futs, off = [], 0
    for s in sizes:
        futs.append(srv.submit("m", mat[off:off + s]))
        off += s
    srv.pump()
    got = np.concatenate([f.result() for f in futs], axis=0)
    direct = _direct_sparse(ridge, mat)
    assert np.array_equal(got, direct)
    assert serve.stats()["eager_requests"] == 0   # stayed on the plan path


@pytest.mark.parametrize("sizes", [(2, 3, 1), (8,), (3, 3, 3, 3, 1),
                                   (1, 1, 1)])
def test_served_rows_equal_predict_on_padded_batch(ridge, sizes):
    """The structural guarantee (geometry-independent): each request's
    served rows are EXACTLY the corresponding rows of ``predict`` on the
    padded bucket batch — same compiled program, same values; padding and
    slicing are bitwise-neutral."""
    reg = _registry(ridge)
    srv = serve.PredictServer(reg)
    payloads = [_rows(s, seed=40 + i) for i, s in enumerate(sizes)]
    futs = [srv.submit("m", p) for p in payloads]
    srv.pump()
    model = reg.get("m")
    bucket = model.spec.bucket_for(sum(sizes), "dense")
    batch = assemble(payloads, bucket)
    direct = np.asarray(ridge.predict(batch).collect())
    off = 0
    for f, s in zip(futs, sizes):
        assert np.array_equal(f.result(), direct[off:off + s])
        off += s


def test_one_row_requests_batch_together(ridge):
    reg = _registry(ridge)
    srv = serve.PredictServer(reg)
    rows = _rows(4, seed=7)
    futs = [srv.submit("m", rows[i]) for i in range(4)]   # 1-D payloads
    srv.pump()
    st = serve.stats()
    assert st["batches"] == 1 and st["batched_requests"] == 4
    got = np.concatenate([f.result() for f in futs], axis=0)
    assert np.array_equal(got, _direct_dense(ridge, rows))


# ---------------------------------------------------------------------------
# steady-state plan-cache discipline (the zero-recompile acceptance)
# ---------------------------------------------------------------------------


def test_steady_state_zero_recompiles(ridge):
    plan_mod.clear_cache()
    reg = _registry(ridge, formats=("dense", "bcoo") if HAVE_SCIPY
                    else ("dense",), nse=4 * N_FEATURES if HAVE_SCIPY
                    else None)
    srv = serve.PredictServer(reg)
    warm = plan_mod.cache_stats()
    assert warm["aot_compiles"] == (6 if HAVE_SCIPY else 3)

    n_requests = 0
    for i in range(6):                       # rotate through both buckets
        futs = [srv.submit("m", _rows(1 + (i % 3), seed=i))
                for _ in range(3)]
        if HAVE_SCIPY:
            futs.append(srv.submit("m", _sparse_rows(2 + (i % 3), seed=i)))
        srv.pump()
        for f in futs:
            f.result()
        n_requests += len(futs)

    after = plan_mod.cache_stats()
    # the serving stream NEVER re-optimized or re-compiled a plan
    assert after["misses"] == warm["misses"]
    assert after["opt_runs"] == warm["opt_runs"]
    assert after["aot_compiles"] == warm["aot_compiles"]
    st = serve.stats()
    assert st["cache_hits"] == n_requests == st["requests"]
    assert st["cache_misses"] == 0
    assert st["batch_sheds"] == 0 and st["failures"] == 0
    lat = st["latency"]
    assert lat["count"] == n_requests and lat["p99_ms"] >= lat["p50_ms"] > 0


def test_warm_is_idempotent(ridge):
    plan_mod.clear_cache()
    reg = _registry(ridge)
    model = reg.get("m")
    assert model.cache.warm() == 0            # already warmed on register
    assert reg.warm_all() == 0
    before = plan_mod.cache_stats()["aot_compiles"]
    plan_mod.clear_cache()
    assert reg.warm_all() == 3                # cold cache -> every bucket
    assert plan_mod.cache_stats()["aot_compiles"] == 3
    assert before == 3


def test_clean_run_recovery_counters_zero(ridge):
    reg = _registry(ridge)
    srv = serve.PredictServer(reg)
    f = srv.submit("m", _rows(3))
    srv.pump()
    f.result()
    st = serve.stats()
    for k in ("batch_sheds", "dispatch_retries", "bucket_fallbacks",
              "cache_misses", "failures", "single_dispatches"):
        assert st[k] == 0, k
    assert st["requests"] == st["responses"] == 1
    assert st["queue_depth"] == 0 and st["queue_depth_peak"] == 1


# ---------------------------------------------------------------------------
# fault-injected serving: the degradation ladder
# ---------------------------------------------------------------------------


def test_transient_dispatch_retries_and_recovers(ridge):
    reg = _registry(ridge)
    srv = serve.PredictServer(reg, policy=RetryPolicy(max_retries=2))
    rows = _rows(5, seed=3)
    with inject(FaultSpec(kind="transient", site="serve_dispatch", times=1)):
        f = srv.submit("m", rows)
        srv.pump()
    assert np.array_equal(f.result(), _direct_dense(ridge, rows))
    st = serve.stats()
    assert st["dispatch_retries"] == 1
    assert st["batch_sheds"] == 0
    assert st["batches"] == 1


def test_batched_fault_sheds_to_unbatched(ridge):
    reg = _registry(ridge)
    srv = serve.PredictServer(reg)
    rows = _rows(6, seed=4)
    # every BATCHED dispatch crashes; single-mode dispatch is clean
    with inject(FaultSpec(kind="crash", site="serve_dispatch", times=None,
                          where={"mode": "batched"})):
        f1 = srv.submit("m", rows[:4])
        f2 = srv.submit("m", rows[4:])
        srv.pump()
    got = np.concatenate([f1.result(), f2.result()], axis=0)
    assert np.array_equal(got, _direct_dense(ridge, rows))
    st = serve.stats()
    assert st["batch_sheds"] == 1
    assert st["single_dispatches"] == 2
    assert st["failures"] == 0


def test_oom_dispatch_sheds_to_unbatched(ridge):
    reg = _registry(ridge)
    srv = serve.PredictServer(reg)
    rows = _rows(3, seed=5)
    with inject(FaultSpec(kind="oom", site="serve_dispatch", times=1,
                          where={"mode": "batched"})):
        f = srv.submit("m", rows)
        srv.pump()
    assert np.array_equal(f.result(), _direct_dense(ridge, rows))
    st = serve.stats()
    assert st["batch_sheds"] == 1 and st["failures"] == 0


def test_plan_level_oom_absorbed_by_resilience_ladder(ridge):
    reg = _registry(ridge)
    srv = serve.PredictServer(reg)
    rows = _rows(4, seed=6)
    with inject(FaultSpec(kind="oom", site="plan_execute", times=1)):
        f = srv.submit("m", rows)
        srv.pump()
    # run_resilient degraded INSIDE the batched dispatch: no shed at all
    assert np.array_equal(f.result(), _direct_dense(ridge, rows))
    st = serve.stats()
    assert st["batch_sheds"] == 0 and st["batches"] == 1


def test_retry_exhaustion_then_shed_recovers(ridge):
    reg = _registry(ridge)
    srv = serve.PredictServer(reg, policy=RetryPolicy(max_retries=1))
    rows = _rows(2, seed=8)
    with inject(FaultSpec(kind="transient", site="serve_dispatch", times=3,
                          where={"mode": "batched"})):
        f = srv.submit("m", rows)
        srv.pump()
    assert np.array_equal(f.result(), _direct_dense(ridge, rows))
    st = serve.stats()
    assert st["dispatch_retries"] == 1       # exhausted, then shed
    assert st["batch_sheds"] == 1


def test_single_mode_failure_is_isolated(ridge):
    reg = _registry(ridge)
    srv = serve.PredictServer(reg)
    rows = _rows(3, seed=9)
    # batched always crashes; the SECOND single dispatch also crashes ->
    # exactly one request fails, its neighbours still get exact answers
    with inject(FaultSpec(kind="crash", site="serve_dispatch", times=None,
                          where={"mode": "batched"}),
                FaultSpec(kind="crash", site="serve_dispatch", at=2, times=1,
                          where={"mode": "single"})):
        futs = [srv.submit("m", rows[i]) for i in range(3)]
        srv.pump()
    # each recovered response is exact vs direct predict of ITS OWN rows
    assert np.array_equal(futs[0].result(), _direct_dense(ridge, rows[:1]))
    with pytest.raises(Exception):
        futs[1].result()
    assert np.array_equal(futs[2].result(), _direct_dense(ridge, rows[2:3]))
    st = serve.stats()
    assert st["failures"] == 1 and st["responses"] == 2


def test_no_fallback_propagates_batch_error(ridge):
    reg = _registry(ridge)
    srv = serve.PredictServer(reg, unbatched_fallback=False)
    with inject(FaultSpec(kind="crash", site="serve_dispatch", times=1)):
        f = srv.submit("m", _rows(2))
        srv.pump()
    with pytest.raises(Exception):
        f.result()
    assert serve.stats()["failures"] == 1


# ---------------------------------------------------------------------------
# out-of-bucket fallbacks
# ---------------------------------------------------------------------------


def test_oversized_request_falls_back_unbatched(ridge):
    reg = _registry(ridge)                       # max bucket: 16 rows
    srv = serve.PredictServer(reg)
    rows = _rows(33, seed=10)
    f = srv.submit("m", rows)
    srv.pump()
    assert np.array_equal(f.result(), _direct_dense(ridge, rows))
    st = serve.stats()
    assert st["bucket_fallbacks"] == 1
    assert st["single_dispatches"] == 1 and st["batches"] == 0


@needs_scipy
def test_bcoo_nse_overflow_falls_back_unbatched(ridge):
    # nse capacity of 4 entries/block, but a nearly-dense request: packing
    # would truncate entries, so the server must go unbatched instead
    reg = _registry(ridge, formats=("dense", "bcoo"), nse=4)
    srv = serve.PredictServer(reg)
    mat = _sparse_rows(4, seed=11, density=0.9)
    assert sparse_mod.max_block_nnz(mat, (4, N_FEATURES)) > 4
    f = srv.submit("m", mat)
    srv.pump()
    assert np.array_equal(f.result(), _direct_sparse(ridge, mat))
    st = serve.stats()
    assert st["bucket_fallbacks"] == 1 and st["failures"] == 0


# ---------------------------------------------------------------------------
# payload validation / batching unit behaviour
# ---------------------------------------------------------------------------


def test_submit_rejects_bad_payloads(ridge):
    srv = serve.PredictServer(_registry(ridge))
    with pytest.raises(ValueError, match="does not match"):
        srv.submit("m", np.zeros((2, N_FEATURES + 1), np.float32))
    with pytest.raises(ValueError, match="empty"):
        srv.submit("m", np.zeros((0, N_FEATURES), np.float32))
    with pytest.raises(KeyError):
        srv.submit("nope", np.zeros((1, N_FEATURES), np.float32))


def test_bucket_spec_selection():
    spec = BucketSpec(8, batch_sizes=(4, 16), block_rows=4)
    assert spec.bucket_for(1, "dense").rows == 4
    assert spec.bucket_for(4, "dense").rows == 4
    assert spec.bucket_for(5, "dense").rows == 16
    assert spec.bucket_for(17, "dense") is None
    assert spec.bucket_for(3, "bcoo") is None      # format not declared
    assert spec.max_rows("dense") == 16
    with pytest.raises(ValueError):
        BucketSpec(8, formats=("bcoo",))           # bcoo without nse
    with pytest.raises(ValueError):
        GeometryBucket(4, 4, 8, "bcoo")


def test_assemble_pads_with_zeros_and_split_inverts():
    bucket = GeometryBucket(rows=8, block_rows=4, n_features=3, fmt="dense")
    a, b = _rows(2, seed=1, m=3), _rows(3, seed=2, m=3)
    x = assemble([a, b], bucket)
    assert x.shape == (8, 3) and x.block_shape == (4, 3)
    dense = np.asarray(x.collect())
    np.testing.assert_array_equal(dense[:2], a)
    np.testing.assert_array_equal(dense[2:5], b)
    np.testing.assert_array_equal(dense[5:], 0.0)
    parts = split_rows(dense, [2, 3])
    np.testing.assert_array_equal(parts[0], a)
    np.testing.assert_array_equal(parts[1], b)


def test_normalize_payload_shapes():
    arr, n, fmt = normalize_payload(np.zeros(5, np.float32), 5)
    assert (n, fmt) == (1, "dense") and arr.shape == (1, 5)
    with pytest.raises(ValueError):
        normalize_payload(np.zeros((2, 3, 4), np.float32), 5)


# ---------------------------------------------------------------------------
# registry: versions, checkpoint round-trip, eager fallback
# ---------------------------------------------------------------------------


def test_registry_versioned_load_roundtrip():
    est1 = _fit_ridge(seed=1)
    est2 = _fit_ridge(seed=2)
    rows = _rows(3, seed=12)
    with tempfile.TemporaryDirectory() as d:
        mdir = os.path.join(d, "ridge")
        est1.save_model(mdir, version=1)
        est2.save_model(mdir, version=2)
        reg = serve.ModelRegistry()
        reg.load("ridge", mdir, version=1, batch_sizes=(4,), block_rows=4)
        reg.load("ridge", mdir, batch_sizes=(4,), block_rows=4)  # newest
        assert reg.versions("ridge") == [1, 2]
        assert reg.get("ridge").version == 2          # latest by default
        srv = serve.PredictServer(reg)
        f1 = srv.submit("ridge", rows, version=1)
        f2 = srv.submit("ridge", rows)
        srv.pump()
        assert np.array_equal(f1.result(), _direct_dense(est1, rows))
        assert np.array_equal(f2.result(), _direct_dense(est2, rows))
        assert not np.array_equal(f1.result(), f2.result())


def test_registry_lists_models(ridge):
    reg = serve.ModelRegistry()
    reg.register("a", ridge, batch_sizes=(4,), warm=False)
    reg.register("a", ridge, version=3, batch_sizes=(4,), warm=False)
    reg.register("b", ridge, batch_sizes=(4,), warm=False)
    assert reg.models() == [("a", 0), ("a", 3), ("b", 0)]
    with pytest.raises(KeyError, match="versions"):
        reg.get("a", version=7)


def test_eager_fallback_estimator_serves_exactly():
    rng = np.random.default_rng(SEED)
    X = rng.normal(size=(96, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int32).reshape(-1, 1)
    est = RandomForestClassifier(n_estimators=4, max_depth=3, seed=0)
    est.fit(from_array(jnp.asarray(X), (32, 6)),
            from_array(jnp.asarray(y), (32, 1)))
    assert not est.has_predict_plan()
    reg = serve.ModelRegistry()
    reg.register("forest", est, batch_sizes=(4, 8), block_rows=4)
    srv = serve.PredictServer(reg)
    rows = X[:5]
    f = srv.submit("forest", rows)
    srv.pump()
    assert np.array_equal(f.result(), _direct_dense(est, rows))
    st = serve.stats()
    assert st["eager_requests"] == 1 and st["cache_hits"] == 0


def test_predict_plan_unsupported_raises():
    est = RandomForestClassifier(n_estimators=2, max_depth=2)
    with pytest.raises(NotImplementedError):
        est._predict_expr(None)


# ---------------------------------------------------------------------------
# threaded server
# ---------------------------------------------------------------------------


def test_threaded_serve_forever_smoke(ridge):
    reg = _registry(ridge)
    rows = _rows(6, seed=13)
    direct = _direct_dense(ridge, rows)
    with serve.PredictServer(reg) as srv:
        futs = [srv.submit("m", rows[i * 2:(i + 1) * 2]) for i in range(3)]
        got = np.concatenate([f.result(timeout=30) for f in futs], axis=0)
    assert np.array_equal(got, direct)
    assert serve.stats()["responses"] == 3


def test_future_timeout():
    f = serve.PredictFuture()
    with pytest.raises(TimeoutError):
        f.result(timeout=0.01)
    assert not f.done()


# ---------------------------------------------------------------------------
# No warmed executable consumes its inputs
#
# Warmed executables live in the shared structural plan cache, so a direct
# ``predict`` whose input has a warmed bucket's geometry binds the same
# executable.  One that donated its batch leaf would delete the caller's
# array there (and a retry would bind a deleted buffer), so none donates.
# ---------------------------------------------------------------------------


def test_donated_warm_serving_output_unchanged(ridge):
    """Warmed executables take no donation: served outputs stay bitwise
    equal to direct predict, the steady-state stream adds zero plan-cache
    misses / opt runs / AOT compiles, and no compiled executable aliases an
    input buffer to its output."""
    plan_mod.clear_cache()
    reg = _registry(ridge)
    srv = serve.PredictServer(reg)
    warm = plan_mod.cache_stats()
    batches, served = [], []
    for i in range(5):
        rows = _rows(1 + (i % 3), seed=40 + i)
        f = srv.submit("m", rows)
        srv.pump()
        batches.append(rows)
        served.append(f.result())
    after = plan_mod.cache_stats()
    assert after["misses"] == warm["misses"]
    assert after["opt_runs"] == warm["opt_runs"]
    assert after["aot_compiles"] == warm["aot_compiles"]
    for p in reg.warmed_plans():
        assert "input_output_alias" not in plan_mod._CACHE[p.key].as_text()
    # direct predict runs at natural geometry (own plans), so only after
    # the frozen-stats window closes
    for rows, got in zip(batches, served):
        assert np.array_equal(got, _direct_dense(ridge, rows))


def test_donation_opt_out_warms_without_aliasing(ridge):
    from repro.serve.compilecache import (PredictCompileCache,
                                          representative_input)

    plan_mod.clear_cache()
    spec = BucketSpec(N_FEATURES, batch_sizes=(4,), block_rows=4)
    cache = PredictCompileCache(ridge, spec)
    assert cache.warm() == 1
    assert cache.warm() == 0
    x = representative_input(spec.buckets()[0])
    assert "input_output_alias" not in \
        plan_mod._CACHE[ridge.predict_plan(x).key].as_text()


def test_direct_predict_of_bucket_shape_keeps_caller_input():
    """One feature makes the predict output ``(gn, 1, bn, 1)`` blocks, the
    batch's own shape, so a donating executable could alias the batch.  A
    direct predict of a bucket-shaped input binds the warmed executable (same
    plan key) and must leave the caller's array alive for a second call."""
    est = _fit_ridge(m=1)
    plan_mod.clear_cache()
    reg = _registry(est, batch_sizes=(4,))
    x = from_array(jnp.asarray(_rows(4, m=1)), (4, 1))
    bucket = reg.get("m").spec.buckets()[0]
    assert est.predict_plan(x).key == reg.get("m").cache.warmed_keys[bucket]
    first = np.asarray(est.predict(x).collect())
    assert not x.blocks.is_deleted()
    second = np.asarray(est.predict(x).collect())
    assert np.array_equal(first, second)
