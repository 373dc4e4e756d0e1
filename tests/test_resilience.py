"""Chaos suite for repro.resilience: every recovery path proven by a
deterministic injected fault.

* injector: counting/Bernoulli determinism, site/mode/where filtering
* run_resilient: retry-then-succeed, retry exhaustion, each rung of the
  fused → eager → einsum degradation ladder (results oracle-checked),
  deterministic errors raising immediately, clean-path zero stats
* guards: finite_report block coordinates (dense + bcoo), pad-state
  awareness (DIRTY pads never false-positive), guard_finite on poisoned
  plan outputs, require_finite_host
* checkpoint satellites: AsyncCheckpointer writer-thread error
  propagation, restore dtype-mismatch raise + allow_cast escape hatch
* estimator fits: CSVM / ALS / KMeans killed mid-fit resume from the
  newest committed iteration and match the uninterrupted fit;
  save_model/load_model round-trips through the registry
* run_with_restarts: deterministic failures stop immediately
"""

import os
import tempfile

import jax.numpy as jnp
import numpy as np
import pytest

import repro.resilience as R
from repro.core import expr as expr_mod
from repro.core import plan as plan_mod
from repro.core.dsarray import PAD_DIRTY, DsArray, from_array
from repro.resilience.inject import _Armed

pytestmark = pytest.mark.resilience

SEED = 20260808


def _lazy_chain(a, b):
    with expr_mod.lazy():
        return (a @ b) * 2.0 + 1.0


def _mats(rng, n=8, k=12, m=6, bs=((4, 4), (4, 3))):
    x = rng.normal(size=(n, k)).astype(np.float32)
    y = rng.normal(size=(k, m)).astype(np.float32)
    return (from_array(x, bs[0]), from_array(y, bs[1]),
            (x @ y) * 2.0 + 1.0)


# counter hygiene is the session-wide autouse obs.reset_all() fixture in
# conftest.py — no per-module reset needed


# ---------------------------------------------------------------------------
# Injector determinism
# ---------------------------------------------------------------------------

def test_injector_counting_determinism():
    spec = R.FaultSpec(kind="transient", site="s", at=3, times=2)
    for _ in range(2):               # identical behaviour on every arming
        with R.inject(spec) as (armed,):
            fired = []
            for i in range(1, 8):
                try:
                    R.maybe_fire("s")
                    fired.append(False)
                except R.TransientError:
                    fired.append(True)
            assert fired == [False, False, True, True,
                             False, False, False]
            assert armed.hits == 7 and armed.fired == 2


def test_injector_bernoulli_replay():
    spec = R.FaultSpec(kind="oom", site="s", p=0.5, seed=123)

    def draw():
        seq = []
        with R.inject(spec):
            for _ in range(32):
                try:
                    R.maybe_fire("s")
                    seq.append(0)
                except R.OOMError:
                    seq.append(1)
        return seq

    first = draw()
    assert first == draw()           # seeded: exact replay
    assert 0 < sum(first) < 32       # and actually Bernoulli, not constant
    # a different seed gives a different (deterministic) schedule
    other = _Armed(R.FaultSpec(kind="oom", site="s", p=0.5, seed=124))
    assert [other.arrive() for _ in range(32)] != [bool(v) for v in first]


def test_injector_site_mode_where_filters():
    with R.inject(
            R.FaultSpec(kind="transient", site="a", modes=("fused",)),
            R.FaultSpec(kind="crash", site="b",
                        where={"estimator": "X", "iteration": 2},
                        times=None)):
        R.maybe_fire("a", mode="eager")          # wrong mode: no fire
        R.maybe_fire("b", estimator="X", iteration=1)   # wrong where
        R.maybe_fire("b", estimator="Y", iteration=2)   # wrong where
        with pytest.raises(R.TransientError):
            R.maybe_fire("a", mode="fused")
        with pytest.raises(R.CrashError):
            R.maybe_fire("b", estimator="X", iteration=2)
    R.maybe_fire("a", mode="fused")              # disarmed outside the block


def test_classify_error_taxonomy():
    ce = R.classify_error
    assert ce(R.TransientError("x")) == R.TRANSIENT
    assert ce(R.OOMError("x")) == R.OOM
    assert ce(MemoryError()) == R.OOM
    assert ce(R.CrashError("x")) == R.DETERMINISTIC
    assert ce(R.NumericalDivergence("nan")) == R.DETERMINISTIC
    assert ce(ValueError("bad shape")) == R.DETERMINISTIC
    # opaque runtime errors classify by status message
    assert ce(RuntimeError("RESOURCE_EXHAUSTED: out of memory")) == R.OOM
    assert ce(RuntimeError("UNAVAILABLE: socket closed")) == R.TRANSIENT
    # unknowns take the caller's default
    assert ce(RuntimeError("boom")) == R.DETERMINISTIC
    assert ce(RuntimeError("boom"), default=R.TRANSIENT) == R.TRANSIENT


def test_classify_kernel_compile_errors_deterministic():
    """A kernel the TPU compiler refuses must raise, not walk the ladder
    down to einsum: only device-memory exhaustion is OOM."""
    ce = R.classify_error
    vmem = RuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
        "allocating on stack for %stacked_matmul.1 = f32[2,1,12000,512]")
    mosaic = RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: "
                          "Invalid vector type for load")
    hbm = RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying to "
                       "allocate 17179869184 bytes.")
    assert ce(vmem) == R.DETERMINISTIC
    assert ce(mosaic) == R.DETERMINISTIC
    assert ce(hbm) == R.OOM


# ---------------------------------------------------------------------------
# run_resilient: retry + degradation ladder
# ---------------------------------------------------------------------------

def test_clean_path_zero_stats():
    rng = np.random.default_rng(SEED)
    a, b, want = _mats(rng)
    out = R.run_resilient(_lazy_chain(a, b), guard="finite")
    np.testing.assert_allclose(np.asarray(out.collect()), want, rtol=1e-5)
    s = R.stats()
    assert s["retries"] == 0 and s["degradations"] == 0
    assert s["recoveries"] == 0 and s["guard_failures"] == 0
    assert s["executions"] == 1


def test_transient_retry_then_succeed():
    rng = np.random.default_rng(SEED + 1)
    a, b, want = _mats(rng)
    with R.inject(R.FaultSpec(kind="transient", site="plan_execute", at=1)):
        out = R.run_resilient(_lazy_chain(a, b))
    np.testing.assert_allclose(np.asarray(out.collect()), want, rtol=1e-5)
    s = R.stats()
    assert s["retries"] == 1 and s["recoveries"] == 1
    assert s["degradations"] == 0


def test_transient_retry_exhaustion():
    rng = np.random.default_rng(SEED + 2)
    a, b, _ = _mats(rng)
    lz = _lazy_chain(a, b)
    with R.inject(R.FaultSpec(kind="transient", site="plan_execute",
                              times=None)):
        with pytest.raises(R.TransientError):
            R.run_resilient(lz, policy=R.RetryPolicy(max_retries=2))
    assert R.stats()["retries"] == 2


def test_retry_backoff_schedule():
    pol = R.RetryPolicy(backoff=0.1, backoff_factor=2.0, max_backoff=0.35)
    assert [pol.delay(i) for i in (1, 2, 3, 4)] == [0.1, 0.2, 0.35, 0.35]
    assert R.RetryPolicy().delay(1) == 0.0       # no sleeps by default


def test_deterministic_raises_immediately():
    rng = np.random.default_rng(SEED + 3)
    a, b, _ = _mats(rng)
    lz = _lazy_chain(a, b)
    with R.inject(R.FaultSpec(kind="crash", site="plan_execute",
                              times=None)):
        with pytest.raises(R.CrashError):
            R.run_resilient(lz)
    s = R.stats()
    assert s["retries"] == 0 and s["degradations"] == 0


def test_oom_degrades_to_eager():
    rng = np.random.default_rng(SEED + 4)
    a, b, want = _mats(rng)
    before = plan_mod.cache_stats()["eager_launches"]
    with R.inject(R.FaultSpec(kind="oom", site="plan_execute",
                              modes=("fused",), times=None)):
        out = R.run_resilient(_lazy_chain(a, b))
    np.testing.assert_allclose(np.asarray(out.collect()), want, rtol=1e-5)
    assert R.stats()["degradations"] == 1
    assert plan_mod.cache_stats()["eager_launches"] == before + 1


def test_oom_degrades_to_einsum():
    rng = np.random.default_rng(SEED + 5)
    a, b, want = _mats(rng)
    with R.inject(R.FaultSpec(kind="oom", site="plan_execute",
                              modes=("fused", "eager"), times=None)):
        out = R.run_resilient(_lazy_chain(a, b))
    np.testing.assert_allclose(np.asarray(out.collect()), want, rtol=1e-5)
    s = R.stats()
    assert s["degradations"] == 2 and s["recoveries"] == 1


def test_oom_ladder_exhausted():
    rng = np.random.default_rng(SEED + 6)
    a, b, _ = _mats(rng)
    lz = _lazy_chain(a, b)
    with R.inject(R.FaultSpec(kind="oom", site="plan_execute", times=None)):
        with pytest.raises(R.OOMError):
            R.run_resilient(lz)
    assert R.stats()["degradations"] == 2        # rode the ladder down first


def test_execute_eager_matches_fused():
    rng = np.random.default_rng(SEED + 7)
    a, b, want = _mats(rng)
    p = plan_mod.plan_for(_lazy_chain(a, b))
    fused = p.execute()[0]
    eager = p.execute_eager()[0]
    einsum = p.execute_eager(backend="einsum")[0]
    for got in (fused, eager, einsum):
        np.testing.assert_allclose(np.asarray(got.collect()), want,
                                   rtol=1e-5)
    assert os.environ.get("REPRO_GEMM") is None or \
        os.environ.get("REPRO_GEMM") != "einsum"   # override was scoped


def test_multi_root_and_prepared_plan():
    rng = np.random.default_rng(SEED + 8)
    a, b, _ = _mats(rng)
    with expr_mod.lazy():
        s1 = (a * 2.0).sum()
        s2 = (a * 2.0).mean()
    o1, o2 = R.run_resilient(s1, s2)
    assert np.isclose(float(o1), 2.0 * np.asarray(a.collect()).sum())
    assert np.isclose(float(o2), 2.0 * np.asarray(a.collect()).mean())


# ---------------------------------------------------------------------------
# Numerical guards
# ---------------------------------------------------------------------------

def test_finite_report_dense_coordinates():
    a = from_array(np.ones((5, 7), np.float32), (2, 3))
    assert a.finite_report().ok
    bad = R.poison_block(a, (1, 2))
    rep = bad.finite_report()
    assert not rep.ok and len(rep.bad_blocks) == 1
    bb = rep.bad_blocks[0]
    assert (bb.gi, bb.gj) == (1, 2) and bb.n_nan == 1 and bb.n_inf == 0
    assert "block (1, 2)" in rep.describe()
    inf_bad = R.poison_block(a, (0, 0), value=np.inf)
    assert inf_bad.finite_report().bad_blocks[0].n_inf == 1


def test_finite_report_dirty_pad_no_false_positive():
    # NaN strictly in the pad region of a DIRTY-pad array: not a divergence
    a = from_array(np.ones((3, 3), np.float32), (2, 2))
    blocks = np.asarray(a.blocks).copy()
    blocks[1, 1, 1, 1] = np.nan                  # pad corner (row 3, col 3)
    dirty = DsArray(jnp.asarray(blocks), a.grid, PAD_DIRTY)
    assert dirty.finite_report().ok
    assert R.all_finite(dirty)
    R.guard_finite(dirty)                        # no raise
    # ... but a NaN inside the logical shape still reports
    blocks[0, 0, 1, 0] = np.nan
    dirty2 = DsArray(jnp.asarray(blocks), a.grid, PAD_DIRTY)
    rep = dirty2.finite_report()
    assert [(b.gi, b.gj) for b in rep.bad_blocks] == [(0, 0)]
    assert rep.bad_blocks[0].first == (1, 0)


def test_finite_report_bcoo_slot():
    a = from_array(np.eye(6, dtype=np.float32), (3, 3)).tosparse()
    assert a.finite_report().ok
    bad = R.poison_block(a, (1, 1))
    rep = bad.finite_report()
    assert not rep.ok and rep.block_format == "bcoo"
    bb = rep.bad_blocks[0]
    assert (bb.gi, bb.gj) == (1, 1) and bb.sparse
    assert "slot" in bb.describe()


def test_guard_finite_on_poisoned_plan_output():
    rng = np.random.default_rng(SEED + 9)
    a, b, _ = _mats(rng)
    with R.inject(R.FaultSpec(kind="poison", site="plan_result",
                              block=(0, 1))):
        with pytest.raises(R.NumericalDivergence) as ei:
            R.run_resilient(_lazy_chain(a, b), guard="finite")
    assert "block (0, 1)" in str(ei.value)
    assert ei.value.report is not None
    assert R.stats()["guard_failures"] == 1


def test_require_finite_host():
    ok = np.arange(4.0)
    assert R.require_finite_host(ok, "x") is ok
    with pytest.raises(R.NumericalDivergence, match="1 nan"):
        R.require_finite_host(np.array([1.0, np.nan]), "solver out")
    # integer arrays pass trivially
    R.require_finite_host(np.arange(3), "ints")


def test_linear_solver_divergence_falls_back():
    # a singular system: solve() yields inf/nan or raises; the unified
    # guard must route both to the lstsq fallback, not crash the fit
    from repro.estimators import LinearRegression
    x = np.ones((12, 3), np.float32)             # rank-1: singular Gram
    y = np.arange(12.0)
    est = LinearRegression(alpha=0.0).fit(x, y)
    assert np.isfinite(np.asarray(est.coef_)).all()


def test_io_load_injection():
    import repro.core.io as rio
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "x.npy")
        np.save(p, np.ones((4, 4), np.float32))
        loaded = rio.load_npy_rows(p, (2, 2))
        assert loaded.shape == (4, 4)
        with R.inject(R.FaultSpec(kind="io", site="io_load")):
            with pytest.raises(R.IOLoadError):
                rio.load_npy_rows(p, (2, 2))
        # IOLoadError is an OSError: existing OSError handling catches it
        assert issubclass(R.IOLoadError, OSError)


# ---------------------------------------------------------------------------
# Checkpoint satellites
# ---------------------------------------------------------------------------

def test_async_checkpointer_error_propagates(tmp_path):
    from repro.checkpoint import AsyncCheckpointer, CheckpointWriteError
    bad_root = os.path.join(str(tmp_path), "afile")
    with open(bad_root, "w") as f:
        f.write("not a directory")               # save() will explode
    ac = AsyncCheckpointer(bad_root)
    ac.save(1, {"w": np.ones(3)})
    with pytest.raises(CheckpointWriteError):
        ac.wait()
    assert ac.last_committed is None             # never lied about a commit
    ac.wait()                                    # error consumed: no re-raise


def test_async_checkpointer_error_from_next_save(tmp_path):
    from repro.checkpoint import AsyncCheckpointer, CheckpointWriteError
    bad_root = os.path.join(str(tmp_path), "afile2")
    with open(bad_root, "w") as f:
        f.write("x")
    ac = AsyncCheckpointer(bad_root)
    ac.save(1, {"w": np.ones(3)})
    import time
    for _ in range(100):                         # let the writer die
        if ac._thread is not None and not ac._thread.is_alive():
            break
        time.sleep(0.01)
    with pytest.raises(CheckpointWriteError):
        ac.save(2, {"w": np.ones(3)})


def test_restore_dtype_mismatch_raises(tmp_path):
    from repro.checkpoint import restore, save
    root = str(tmp_path)
    save(root, 0, {"w": np.ones(4, np.int32)})
    with pytest.raises(ValueError, match="dtype mismatch"):
        restore(root, 0, {"w": np.ones(4, np.float32)})
    out = restore(root, 0, {"w": np.ones(4, np.float32)}, allow_cast=True)
    assert np.asarray(out["w"]).dtype == np.float32
    same = restore(root, 0, {"w": np.ones(4, np.int32)})
    assert np.asarray(same["w"]).dtype == np.int32


def test_run_with_restarts_stops_on_deterministic(tmp_path):
    from repro.distributed.fault_tolerance import run_with_restarts

    calls = []

    def step(state, i):
        calls.append(i)
        if i == 2:
            raise R.NumericalDivergence("loss went NaN")
        return state + 1, {"loss": float(state)}

    with pytest.raises(R.NumericalDivergence):
        run_with_restarts(init_state=lambda: 0, step_fn=step,
                          ckpt_root=str(tmp_path), total_steps=6,
                          ckpt_every=2, max_failures=3)
    # no restart loop: the NaN step ran exactly once
    assert calls.count(2) == 1


# ---------------------------------------------------------------------------
# Checkpointable fits + model registry
# ---------------------------------------------------------------------------

def _svm_data():
    rng = np.random.default_rng(SEED + 10)
    x = rng.normal(size=(96, 6)).astype(np.float32)
    w = rng.normal(size=6)
    y = (x @ w > 0).astype(np.float64)
    return from_array(x, (32, 3)), y


def test_csvm_crash_resume_matches_uninterrupted():
    from repro.estimators import CascadeSVM
    xd, y = _svm_data()
    ref = CascadeSVM(max_iter=5, tol=1e-12).fit(xd, y)
    pred_ref = np.asarray(ref.predict(xd).collect()).ravel()
    with tempfile.TemporaryDirectory() as d:
        interrupted = CascadeSVM(max_iter=5, tol=1e-12)
        with R.inject(R.FaultSpec(kind="crash", site="fit_iteration",
                                  where={"iteration": 3})):
            with pytest.raises(R.CrashError):
                interrupted.fit(xd, y, checkpoint_dir=d)
        resumed = CascadeSVM(max_iter=5, tol=1e-12)
        resumed.fit(xd, y, checkpoint_dir=d, resume=d)
        assert resumed.n_iter_ == ref.n_iter_
        assert resumed.n_sv_ == ref.n_sv_
        np.testing.assert_allclose(np.asarray(resumed.sv_),
                                   np.asarray(ref.sv_))
        np.testing.assert_allclose(np.asarray(resumed.dual_coef_),
                                   np.asarray(ref.dual_coef_))
        pred_res = np.asarray(resumed.predict(xd).collect()).ravel()
        assert (pred_ref == pred_res).all()


def test_als_crash_resume_matches_uninterrupted():
    from repro.algorithms import ALS
    rng = np.random.default_rng(SEED + 11)
    rd = from_array((rng.random((40, 24)) * 5).astype(np.float32), (16, 8))
    ref = ALS(n_factors=4, max_iter=4, tol=1e-12, seed=3).fit(rd)
    with tempfile.TemporaryDirectory() as d:
        interrupted = ALS(n_factors=4, max_iter=4, tol=1e-12, seed=3)
        with R.inject(R.FaultSpec(kind="crash", site="fit_iteration",
                                  where={"iteration": 3})):
            with pytest.raises(R.CrashError):
                interrupted.fit(rd, checkpoint_dir=d)
        resumed = ALS(n_factors=4, max_iter=4, tol=1e-12, seed=3)
        resumed.fit(rd, checkpoint_dir=d, resume=d)
        assert resumed.n_iter_ == ref.n_iter_
        np.testing.assert_allclose(np.asarray(resumed.u_.collect()),
                                   np.asarray(ref.u_.collect()), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(resumed.v_.collect()),
                                   np.asarray(ref.v_.collect()), rtol=1e-5)


def test_kmeans_crash_resume():
    from repro.algorithms import KMeans
    rng = np.random.default_rng(SEED + 12)
    x = rng.normal(size=(60, 5)).astype(np.float32)
    x[:30] += 4.0
    xd = from_array(x, (16, 5))
    ref = KMeans(n_clusters=3, max_iter=8, seed=7).fit(xd)
    with tempfile.TemporaryDirectory() as d:
        interrupted = KMeans(n_clusters=3, max_iter=8, seed=7)
        with R.inject(R.FaultSpec(kind="crash", site="fit_iteration",
                                  where={"iteration": 2})):
            with pytest.raises(R.CrashError):
                interrupted.fit(xd, checkpoint_dir=d)
        resumed = KMeans(n_clusters=3, max_iter=8, seed=7)
        resumed.fit(xd, checkpoint_dir=d, resume=d)
        assert resumed.n_iter_ == ref.n_iter_
        np.testing.assert_allclose(np.asarray(resumed.centers_),
                                   np.asarray(ref.centers_), rtol=1e-5)


def test_save_load_model_registry():
    from repro.estimators import CascadeSVM, load_model
    from repro.estimators.base import BaseEstimator, NotFittedError
    xd, y = _svm_data()
    svm = CascadeSVM(max_iter=3, tol=1e-12).fit(xd, y)
    pred_ref = np.asarray(svm.predict(xd).collect()).ravel()
    with tempfile.TemporaryDirectory() as d:
        svm.save_model(d)
        # registry dispatch (class name from the manifest)
        again = load_model(d)
        assert type(again) is CascadeSVM
        assert again.get_params() == svm.get_params()
        pred = np.asarray(again.predict(xd).collect()).ravel()
        assert (pred == pred_ref).all()
        # dtype fidelity through the manifest-derived protos
        assert np.asarray(again.sv_).dtype == np.asarray(svm.sv_).dtype
        # concrete-class load checks the manifest
        from repro.estimators import LinearRegression
        with pytest.raises(ValueError, match="CascadeSVM"):
            LinearRegression.load_model(d)
    with pytest.raises(NotFittedError):
        CascadeSVM().save_model("/tmp/never-written")


def test_save_load_model_algorithms_lazy_registry():
    # an algorithms-package estimator resolves through the lazy registry
    from repro.algorithms import KMeans
    from repro.estimators import load_model
    rng = np.random.default_rng(SEED + 13)
    xd = from_array(rng.normal(size=(30, 4)).astype(np.float32), (10, 4))
    km = KMeans(n_clusters=2, max_iter=5, seed=1).fit(xd)
    with tempfile.TemporaryDirectory() as d:
        km.save_model(d)
        back = load_model(d)
        assert type(back) is KMeans
        np.testing.assert_allclose(np.asarray(back.centers_),
                                   np.asarray(km.centers_))
        assert back.n_iter_ == km.n_iter_


def test_fit_checkpoint_wrong_estimator_rejected(tmp_path):
    from repro.estimators.base import _FitCheckpoint
    a = _FitCheckpoint(str(tmp_path), "CascadeSVM")
    a.save(1, {"w": np.ones(3, np.float32), "obj": 1.5})
    with pytest.raises(ValueError, match="CascadeSVM"):
        _FitCheckpoint(str(tmp_path), "ALS").load()
    it, st = a.load()
    assert it == 1 and st["obj"] == 1.5
    assert np.asarray(st["w"]).dtype == np.float32


def test_clean_fit_keeps_plan_cache_regression():
    # the checkpointing machinery must not disturb the hot-loop plan cache:
    # a clean CSVM fit still optimizes its kernel-block plan exactly once
    from repro.estimators import CascadeSVM
    xd, y = _svm_data()
    plan_mod.clear_cache()
    CascadeSVM(max_iter=5, tol=1e-12).fit(xd, y)
    st = plan_mod.cache_stats()
    assert st["opt_runs"] == 1
    assert st["eager_launches"] == 0             # ladder never engaged
    s = R.stats()
    assert s["retries"] == 0 and s["degradations"] == 0
