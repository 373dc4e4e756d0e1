"""Estimator subsystem: contract sweep, oracle agreement, and the ISSUE-5
acceptance assertions.

Four families:

* **contract sweep** — every estimator (new subsystem + the refactored
  algorithms classes) round-trips ``get_params``/``set_params``, rejects
  unknown params, is deterministic under a fixed seed, and accepts dense,
  bcoo and ragged-grid ds-array inputs with consistent results;
* **oracle agreement** — CSVM vs ``sklearn.svm.SVC`` (prediction
  agreement), Ridge vs ``sklearn.linear_model.Ridge`` (coefficient
  equality), forest accuracy floor; the sklearn tests skip cleanly when it
  is not installed (optional dev dependency);
* **acceptance** — CSVM ``fit`` on a bcoo input never densifies the data
  matrix (``sparse.todense`` never sees an array of the data's shape, and
  the recorded kernel-block plan's jaxpr contains no dense-stacked-x-shaped
  intermediate), and a 5-iteration recorded fit loop optimizes its plan
  exactly once (``opt_runs == 1``, like the PR-4 hot-loop regression);
* **solver behaviour** — LinearRegression's TSQR fallback fires on
  ill-conditioned tall-skinny inputs and matches the normal-equation path
  on well-conditioned ones.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import repro
from repro.core import DsArray, from_array, plan
from repro.core import sparse as sparse_mod
from repro.algorithms import ALS, KMeans, PCA
from repro.estimators import (BaseEstimator, CascadeSVM, LinearRegression,
                              NotFittedError, RandomForestClassifier, Ridge)

pytestmark = pytest.mark.estimators

RNG = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# Fixed small datasets
# ---------------------------------------------------------------------------


def two_blobs(seed=0, n_per=60, d=4, sep=3.0):
    rng = np.random.default_rng(seed)
    a = rng.normal(-sep / 2, 1.0, size=(n_per, d))
    b = rng.normal(sep / 2, 1.0, size=(n_per, d))
    x = np.concatenate([a, b]).astype(np.float32)
    y = np.concatenate([np.zeros(n_per), np.ones(n_per)]).astype(np.int32)
    idx = rng.permutation(len(x))
    return x[idx], y[idx]


def three_blobs(seed=0, n_per=50, d=4, spread=8.0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(3, d)).astype(np.float32) * spread
    x = np.concatenate([rng.normal(c, 0.5, size=(n_per, d)).astype(np.float32)
                        for c in centers])
    y = np.repeat(np.arange(3), n_per).astype(np.int32)
    idx = rng.permutation(len(x))
    return x[idx], y[idx]


def regression_data(seed=0, n=150, m=5, noise=0.05):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, m)).astype(np.float32)
    coef = rng.normal(size=m).astype(np.float32)
    y = (x @ coef + 0.5 + noise * rng.normal(size=n)).astype(np.float32)
    return x, y, coef


def sparse_two_blobs(seed=0, n_per=60, d=8):
    """Two classes separable on sparse 'topic' features (text-like):
    background activity everywhere, strong loadings on each class's own
    topic half — ~46% dense."""
    rng = np.random.default_rng(seed)
    x = np.where(rng.random((2 * n_per, d)) < 0.8, 0.0,
                 np.abs(rng.normal(size=(2 * n_per, d)))).astype(np.float32)
    sig = ((rng.random((2 * n_per, d // 2)) < 0.6) *
           np.abs(rng.normal(size=(2 * n_per, d // 2))) * 4.0)
    x[:n_per, : d // 2] += sig[:n_per].astype(np.float32)
    x[n_per:, d // 2:] += sig[n_per:].astype(np.float32)
    y = np.concatenate([np.zeros(n_per), np.ones(n_per)]).astype(np.int32)
    idx = rng.permutation(len(x))
    return x[idx], y[idx]


# (name, factory, dataset builder) — the contract-sweep registry.  Block
# shape 32x<d> for the canonical grid; the ragged case re-blocks oddly.
def _svm_linear():
    return CascadeSVM(kernel="linear", sv_cap=32, max_iter=3)


def _svm_rbf():
    return CascadeSVM(kernel="rbf", sv_cap=32, max_iter=3)


ESTIMATORS = [
    ("csvm_linear", _svm_linear, two_blobs),
    ("csvm_rbf", _svm_rbf, two_blobs),
    ("linreg", lambda: LinearRegression(),
     lambda: regression_data()[:2]),
    ("ridge", lambda: Ridge(alpha=0.5),
     lambda: regression_data()[:2]),
    ("forest", lambda: RandomForestClassifier(n_estimators=6, max_depth=5,
                                              seed=3),
     three_blobs),
    ("kmeans", lambda: KMeans(n_clusters=3, max_iter=20, seed=0),
     lambda: (three_blobs()[0], None)),
    ("pca", lambda: PCA(n_components=2, n_iter=30),
     lambda: (three_blobs()[0], None)),
    ("als", lambda: ALS(n_factors=3, reg=1e-3, max_iter=8, tol=1e-6),
     lambda: ((np.random.default_rng(3).normal(size=(48, 3)) @
               np.random.default_rng(4).normal(size=(3, 40)))
              .astype(np.float32), None)),
]

IDS = [e[0] for e in ESTIMATORS]


def _fit(est, x, y, block=(32, None)):
    bn, bm = block
    xd = from_array(x, (bn, bm or x.shape[1]))
    return est.fit(xd, y) if y is not None else est.fit(xd), xd


def _fitted_signature(est, xd):
    """Comparable summary of a fitted model: predictions where the estimator
    predicts rows, else its fitted arrays."""
    if isinstance(est, (CascadeSVM, RandomForestClassifier, LinearRegression,
                        KMeans)):
        return np.asarray(est.predict(xd).collect()).ravel()
    if isinstance(est, PCA):
        return np.asarray(est.components_)
    if isinstance(est, ALS):
        return np.asarray((est.u_ @ est.v_.T).collect())
    raise AssertionError(type(est))


# ---------------------------------------------------------------------------
# Contract: params round-trip, determinism, input formats
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,factory,data", ESTIMATORS, ids=IDS)
def test_params_roundtrip(name, factory, data):
    est = factory()
    params = est.get_params()
    # every param is a constructor arg; a clone built from them is identical
    clone = type(est)(**params)
    assert clone.get_params() == params
    # set_params round-trips and chains
    assert est.set_params(**params) is est
    assert est.get_params() == params
    # fitted state is never a param
    assert not any(k.endswith("_") for k in params)
    with pytest.raises(ValueError):
        est.set_params(definitely_not_a_param=1)


@pytest.mark.parametrize("name,factory,data", ESTIMATORS, ids=IDS)
def test_deterministic_under_fixed_seed(name, factory, data):
    out = data()
    x, y = out if isinstance(out, tuple) else (out, None)
    a, xd = _fit(factory(), x, y)
    b, _ = _fit(factory(), x, y)
    np.testing.assert_array_equal(_fitted_signature(a, xd),
                                  _fitted_signature(b, xd))


@pytest.mark.parametrize("name,factory,data", ESTIMATORS, ids=IDS)
def test_accepts_dense_bcoo_and_ragged_grids(name, factory, data):
    out = data()
    x, y = out if isinstance(out, tuple) else (out, None)
    ref, ref_xd = _fit(factory(), x, y)
    ref_sig = _fitted_signature(ref, ref_xd)

    def check(sig, label):
        if name in ("kmeans", "csvm_linear", "csvm_rbf", "forest"):
            # discrete outputs: allow a sliver of boundary flips
            agree = (np.asarray(ref_sig) == np.asarray(sig)).mean()
            assert agree > 0.9, (name, label, agree)
        elif name == "als":
            # blocking changes the per-block random init, so compare each
            # factorization against the ratings matrix it reconstructs
            rmse = float(np.sqrt(((sig - x) ** 2).mean()))
            assert rmse < 0.1, (label, rmse)
        else:
            np.testing.assert_allclose(np.abs(ref_sig), np.abs(sig),
                                       rtol=5e-2, atol=5e-2,
                                       err_msg=f"{name}/{label}")

    # ragged block grid: same data, awkward blocking — same model
    bn, bm = 17, max(1, x.shape[1] - 1)
    xr = from_array(x, (bn, bm))
    rag = factory().fit(xr, y) if y is not None else factory().fit(xr)
    check(_fitted_signature(rag, ref_xd), "ragged")

    # bcoo input: fit must accept it and stay near the dense model
    xs = from_array(x, (32, x.shape[1])).tosparse()
    sp = factory().fit(xs, y) if y is not None else factory().fit(xs)
    check(_fitted_signature(sp, ref_xd), "bcoo")


def test_predict_before_fit_raises():
    for est, args in ((CascadeSVM(), (from_array(np.ones((4, 2)), (2, 2)),)),
                      (LinearRegression(),
                       (from_array(np.ones((4, 2)), (2, 2)),)),
                      (RandomForestClassifier(),
                       (from_array(np.ones((4, 2)), (2, 2)),)),
                      (KMeans(), (from_array(np.ones((4, 2)), (2, 2)),)),
                      (ALS(), (0, 0))):
        with pytest.raises(NotFittedError):
            est.predict(*args)


def test_validation_rejects_bad_inputs():
    x, y = two_blobs()
    xd = from_array(x, (32, 4))
    with pytest.raises(ValueError):
        CascadeSVM().fit(xd, y[:-3])          # length mismatch
    with pytest.raises(ValueError):
        CascadeSVM().fit(np.ones((4, 2, 2)), [1, 0, 1, 0])   # not 2-D
    with pytest.raises(ValueError):
        CascadeSVM().fit(xd, np.zeros_like(y))               # one class
    with pytest.raises(ValueError):
        CascadeSVM(kernel="poly").fit(xd, y)
    with pytest.raises(ValueError):
        LinearRegression(solver="qr").fit(xd, y.astype(np.float32))
    # raw ndarray x is accepted and blocked automatically
    est = LinearRegression().fit(x, y.astype(np.float32))
    assert est.coef_ is not None
    # predict returns the conventional (n, 1) ds-array
    out = est.predict(xd)
    assert isinstance(out, DsArray) and out.shape == (len(x), 1)


# ---------------------------------------------------------------------------
# Oracle agreement (sklearn optional)
# ---------------------------------------------------------------------------


def test_csvm_matches_sklearn_svc():
    svm = pytest.importorskip("sklearn.svm")
    x, y = two_blobs(seed=1)
    xd = from_array(x, (32, 4))
    for kernel in ("linear", "rbf"):
        ours = CascadeSVM(kernel=kernel, c=1.0, sv_cap=48).fit(xd, y)
        theirs = svm.SVC(kernel=kernel, C=1.0, gamma="scale").fit(x, y)
        pred = np.asarray(ours.predict(xd).collect()).ravel()
        agree = (pred == theirs.predict(x)).mean()
        assert agree >= 0.95, (kernel, agree)
        assert ours.score(xd, y) >= 0.95


def test_ridge_matches_sklearn():
    linear_model = pytest.importorskip("sklearn.linear_model")
    x, y, _ = regression_data(seed=2)
    ours = Ridge(alpha=2.0).fit(from_array(x, (32, 5)), y)
    theirs = linear_model.Ridge(alpha=2.0).fit(x, y)
    np.testing.assert_allclose(ours.coef_, theirs.coef_, atol=1e-4)
    assert abs(ours.intercept_ - theirs.intercept_) < 1e-4


def test_forest_accuracy_floor():
    x, y = three_blobs(seed=5, n_per=80)
    xtr, ytr = x[:180], y[:180]
    xte, yte = x[180:], y[180:]          # held-out rows of the SAME blobs
    f = RandomForestClassifier(n_estimators=8, max_depth=6, seed=0).fit(
        from_array(xtr, (32, 4)), ytr)
    assert f.score(from_array(xtr, (32, 4)), ytr) >= 0.95
    assert f.score(from_array(xte, (32, 4)), yte) >= 0.85


def test_linreg_tsqr_fallback_on_ill_conditioned():
    rng = np.random.default_rng(0)
    base = rng.normal(size=(120, 3)).astype(np.float32)
    x = np.concatenate(
        [base, base + 1e-4 * rng.normal(size=base.shape).astype(np.float32)],
        axis=1)
    y = x.sum(axis=1).astype(np.float32)
    est = LinearRegression().fit(from_array(x, (32, 3)), y)
    assert est.solver_used_ == "tsqr"
    assert est.score(from_array(x, (32, 3)), y) > 0.999
    # well-conditioned input keeps the one-plan normal equations
    xw, yw, coef = regression_data(seed=3, noise=0.0)
    est2 = LinearRegression().fit(from_array(xw, (32, 5)), yw)
    assert est2.solver_used_ == "normal"
    np.testing.assert_allclose(est2.coef_, coef, atol=1e-4)
    # Ridge regularizes instead of falling back
    est3 = Ridge(alpha=1.0).fit(from_array(x, (32, 3)), y)
    assert est3.solver_used_ == "normal"


# ---------------------------------------------------------------------------
# Acceptance: sparse-native CSVM + cached fit-loop plan
# ---------------------------------------------------------------------------


from repro.analysis import (  # noqa: E402
    assert_no_densify, walk_eqns)


def test_csvm_sparse_fit_never_densifies_and_caches_plan(monkeypatch):
    """ISSUE-5 acceptance: on a bcoo input (1) no ``todense`` of the data
    matrix anywhere in fit, (2) the recorded kernel-block plan's jaxpr has
    no densified-x intermediate, (3) a 5-iteration fit optimizes its plan
    exactly ONCE and replays the compiled program."""
    x, y = sparse_two_blobs()
    xs = from_array(x, (16, 4)).tosparse()
    assert xs.block_format == "bcoo"

    densified = []
    real_todense = sparse_mod.todense

    def spy(a):
        if getattr(a, "is_sparse", False):
            densified.append(a.shape)
        return real_todense(a)

    monkeypatch.setattr(sparse_mod, "todense", spy)
    plan.clear_cache()
    est = CascadeSVM(kernel="rbf", sv_cap=32, max_iter=5, tol=-1.0)
    est.fit(xs, y)

    # (1) nothing was densified during fit — not the data matrix, not the
    # chunks (the per-node bases go through the O(nnz) rows_to_dense path)
    assert densified == [], densified
    assert est.n_iter_ == 5

    # (3) the per-iteration recorded plan: one optimizer run, 4 structural
    # skips, 4 compiled-plan hits — the PR-4 hot-loop property, now over a
    # whole estimator fit loop
    st = plan.cache_stats()
    assert st["opt_runs"] == 1, st
    assert st["opt_skips"] == 4, st
    assert st["misses"] == 1 and st["hits"] == 4, st

    # (2) the recorded kernel block never materializes dense x: no
    # intermediate in the plan jaxpr has the densified stacked shape
    sv_ds = from_array(jnp.asarray(est.sv_.T),
                       (xs.block_shape[1], est.sv_cap))
    kb = xs.lazy() @ sv_ds
    jx = plan.plan_for(kb).jaxpr()
    dense_shape = xs.blocks.shape
    assert_no_densify(jx, dense_shape)
    prims = {e.primitive.name for e in walk_eqns(jx)}
    assert "bcoo_dot_general" in prims, prims

    # ...and the model still separates the classes
    assert est.score(xs, y) >= 0.9


def test_csvm_sparse_chunks_stay_bcoo():
    """The cascade's row partition is a batch-dim slice of the stacked
    BCOO: chunks keep the bcoo format (no bcoo_todense on the way in)."""
    x, y = sparse_two_blobs(seed=3)
    xs = from_array(x, (16, 4)).tosparse()
    chunk = xs[0:16]
    assert chunk.block_format == "bcoo"
    chunk.check_invariants()
    # and rows_to_dense rebuilds exactly the chunk rows, O(nnz) on the host
    np.testing.assert_allclose(sparse_mod.rows_to_dense(chunk),
                               np.asarray(xs[0:16].todense().collect()))


def test_estimator_fit_predict_lazy_interop():
    """Fitting inside a repro.lazy() context must not corrupt recording
    state: eager driver code (validation, host solvers) runs under the
    recorder only where it records, and results match the eager fit."""
    x, y = two_blobs(seed=9)
    xd = from_array(x, (32, 4))
    eager = CascadeSVM(kernel="linear", sv_cap=32, max_iter=2).fit(xd, y)
    pred_e = np.asarray(eager.predict(xd).collect()).ravel()
    est = CascadeSVM(kernel="linear", sv_cap=32, max_iter=2)
    with repro.lazy():
        est.fit(xd, y)
        pred_l = est.predict(xd)
    np.testing.assert_array_equal(
        np.asarray(pred_l.collect()).ravel(), pred_e)


def test_base_estimator_is_shared_contract():
    """The refactored algorithms classes and the new subsystem share ONE
    base — the whole layer converges on a single estimator contract."""
    for cls in (CascadeSVM, LinearRegression, Ridge, RandomForestClassifier,
                KMeans, ALS, PCA):
        assert issubclass(cls, BaseEstimator), cls


# ---------------------------------------------------------------------------
# Review regressions
# ---------------------------------------------------------------------------


def test_csvm_feedback_loop_actually_iterates():
    """A positive tol must not declare convergence at iteration 1 (there is
    nothing to compare against yet): the cascade feedback loop runs at
    least twice before it may stop."""
    x, y = two_blobs(seed=4)
    est = CascadeSVM(kernel="linear", sv_cap=32, max_iter=4,
                     tol=1e-3).fit(from_array(x, (32, 4)), y)
    assert est.n_iter_ >= 2
    assert est.score(from_array(x, (32, 4)), y) >= 0.95


def test_linreg_tsqr_survives_small_blocks():
    """The tsqr path re-blocks rows when block rows < n_features instead of
    crashing — including when 'auto' picks it on the user's behalf."""
    rng = np.random.default_rng(0)
    base = rng.normal(size=(40, 3)).astype(np.float32)
    x = np.concatenate(
        [base, base + 1e-4 * rng.normal(size=base.shape).astype(np.float32)],
        axis=1)
    y = x.sum(axis=1).astype(np.float32)
    xd = from_array(x, (4, 3))            # block rows (4) < features (6)
    est = LinearRegression().fit(xd, y)
    assert est.solver_used_ == "tsqr"
    assert est.score(xd, y) > 0.999
    est2 = LinearRegression(solver="tsqr").fit(xd, y)
    assert est2.score(xd, y) > 0.999
    # wide inputs (n < m) never take tsqr
    xw = from_array(rng.normal(size=(4, 6)).astype(np.float32), (2, 3))
    yw = np.ones(4, np.float32)
    assert LinearRegression().fit(xw, yw).solver_used_ == "normal"


def test_classifiers_reject_string_labels():
    x, y = two_blobs(seed=2)
    labels = np.where(y == 0, "neg", "pos")
    for est in (CascadeSVM(), RandomForestClassifier()):
        with pytest.raises(ValueError, match="numeric"):
            est.fit(from_array(x, (32, 4)), labels)


def test_all_estimators_fit_inside_ambient_lazy():
    """Every estimator's driver glue masks an ambient repro.lazy() context
    (only the explicit .lazy() lifts record), so fitting inside the context
    manager works and matches the eager fit."""
    x3, y3 = three_blobs(seed=1)
    xd = from_array(x3, (32, 4))
    with repro.lazy():
        km = KMeans(n_clusters=3, max_iter=10, seed=0).fit(xd)
        pc = PCA(n_components=2, n_iter=10).fit(xd)
        _ = pc.transform(xd)
        fr = RandomForestClassifier(n_estimators=4, max_depth=4,
                                    seed=0).fit(xd, y3)
        _ = fr.predict(xd)
        rng = np.random.default_rng(3)
        r = (rng.normal(size=(48, 3)) @ rng.normal(size=(3, 40))
             ).astype(np.float32)
        al = ALS(n_factors=3, reg=1e-3, max_iter=4).fit(from_array(r, (16, 8)))
        _ = al.score(from_array(r, (16, 8)))
    assert km.centers_ is not None and pc.components_ is not None
    assert fr.feat_ is not None and al.u_ is not None


def test_pca_transform_uses_training_mean():
    """transform centers by the mean stored at fit, not the input's own —
    a single training row must project to its training score, not zero."""
    x, _ = three_blobs(seed=2)
    est = PCA(n_components=2, n_iter=30).fit(from_array(x, (32, 4)))
    full = np.asarray(est.transform(from_array(x, (32, 4))).collect())
    one = np.asarray(est.transform(from_array(x[:1], (1, 4))).collect())
    np.testing.assert_allclose(one.ravel(), full[0], rtol=1e-4, atol=1e-4)
    assert np.abs(one).max() > 1e-3          # not the all-zero artifact


def test_ridge_tsqr_keeps_regularization():
    """solver="tsqr" with alpha > 0 factors the augmented [X; sqrt(a)·I]
    system — the penalty is never silently dropped."""
    linear_model = pytest.importorskip("sklearn.linear_model")
    x, y, _ = regression_data(seed=4)
    ours = Ridge(alpha=50.0, solver="tsqr").fit(from_array(x, (32, 5)), y)
    ols = LinearRegression(solver="tsqr").fit(from_array(x, (32, 5)), y)
    sk = linear_model.Ridge(alpha=50.0).fit(x, y)
    np.testing.assert_allclose(ours.coef_, sk.coef_, atol=1e-4)
    # and it is genuinely different from the unregularized QR solve
    assert np.abs(ours.coef_ - ols.coef_).max() > 1e-3


def test_csvm_duplicate_samples_keep_combined_box():
    """Genuine repeated samples combine their box constraints (k·C, like a
    standard SVM); only feedback/merge COPIES are collapsed.  Verified
    against sklearn on a dataset where every row appears twice and C
    binds."""
    svm = pytest.importorskip("sklearn.svm")
    x, y = two_blobs(seed=8, sep=1.5)        # overlapping: C matters
    xd2 = np.repeat(x, 2, axis=0)            # every sample twice
    yd2 = np.repeat(y, 2)
    ours = CascadeSVM(kernel="linear", c=0.05, sv_cap=64,
                      max_iter=3).fit(from_array(xd2, (32, 4)), yd2)
    theirs = svm.SVC(kernel="linear", C=0.05).fit(xd2, yd2)
    pred = np.asarray(ours.predict(from_array(xd2, (32, 4))).collect())
    agree = (pred.ravel() == theirs.predict(xd2)).mean()
    assert agree >= 0.9, agree
    # the dedup really accumulated: some collapsed slot exceeds one C
    assert ours.dual_coef_.max() > 0.05 * (1 + 1e-6)


def test_linreg_rank_deficient_min_norm():
    """An all-zero (or exactly collinear) feature column must not crash the
    alpha=0 solvers: both the normal-equation path (sparse input) and the
    tsqr path (dense input) return the min-norm lstsq solution."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(60, 6)).astype(np.float32)
    x[:, 3] = 0.0                              # dead feature
    w = rng.normal(size=6).astype(np.float32)
    w[3] = 0.0
    y = (x @ w).astype(np.float32)
    for xd in (from_array(x, (16, 6)).tosparse(), from_array(x, (16, 6))):
        est = LinearRegression().fit(xd, y)
        assert np.isfinite(est.coef_).all(), est.solver_used_
        pred = np.asarray(est.predict(xd).collect()).ravel()
        np.testing.assert_allclose(pred, y, atol=1e-3,
                                   err_msg=est.solver_used_)


# ---------------------------------------------------------------------------
# The normal equations on the device: float32 Cholesky, float64 refinement
# ---------------------------------------------------------------------------

def _fit_both(monkeypatch, make, xd, y):
    """``make()`` fitted on the host LU and then on the device path (the
    order threshold patched low), with the solve counters' rise."""
    from repro.estimators import linear
    monkeypatch.setattr(linear, "_DEVICE_SOLVE_MIN", 10 ** 9)
    host = make().fit(xd, y)
    monkeypatch.setattr(linear, "_DEVICE_SOLVE_MIN", 1)
    before = repro.obs.snapshot("solve")
    dev = make().fit(xd, y)
    after = repro.obs.snapshot("solve")
    return host, dev, {k: after[k] - before.get(k, 0) for k in after}


def _theta(est):
    return np.append(est.coef_, est.intercept_)


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
@pytest.mark.parametrize("fit_intercept", [True, False])
@pytest.mark.parametrize("cls, alpha", [(Ridge, 2.0), (LinearRegression, 0.0)])
@pytest.mark.parametrize("m", [17, 130, 300])
def test_device_solve_matches_the_host_lu(m, cls, alpha, fit_intercept,
                                          sparse, monkeypatch):
    rng = np.random.default_rng(m)
    n = 4 * m + 50
    x = (rng.normal(size=(n, m)) + 0.3).astype(np.float32)
    y = (x @ rng.normal(size=m) + rng.normal(size=n)).astype(np.float32)
    xd = from_array(x, (64, 64))
    if sparse:
        xd = xd.tosparse()
    host, dev, rose = _fit_both(
        monkeypatch,
        lambda: cls(alpha=alpha, fit_intercept=fit_intercept), xd, y)
    assert host.solver_used_ == dev.solver_used_ == "normal"
    ref = _theta(host)
    err = np.linalg.norm(_theta(dev) - ref) / np.linalg.norm(ref)
    assert err <= 1e-10, err
    assert rose["solve.device"] == 1 and rose["solve.host_fallback"] == 0
    assert rose["solve.refine_steps"] >= 1


def _cond_1e10(m=12, n=200, seed=0):
    rng = np.random.default_rng(seed)
    u, _ = np.linalg.qr(rng.normal(size=(n, m)))
    v, _ = np.linalg.qr(rng.normal(size=(m, m)))
    x = (u * np.logspace(0, -5, m)) @ v.T           # cond(XᵀX) = 1e10
    y = x @ rng.normal(size=m) + 1e-3 * rng.normal(size=n)
    return x.astype(np.float32), y.astype(np.float32)


@pytest.mark.parametrize("case", ["rank_1", "cond_1e10", "no_progress"])
def test_device_solve_falls_back_to_the_host_lu(case, monkeypatch):
    """A non-finite float32 factor (a singular Gram; cond 1e10, beyond
    float32) or a refinement whose backward error stops falling (here a
    correction of the wrong sign) ends on the host path, as before."""
    from repro.estimators import linear
    if case == "no_progress":
        solve = linear._factor_solve
        monkeypatch.setattr(linear, "_factor_solve",
                            lambda chol, r: -solve(chol, r))
        x, y, _ = regression_data(seed=5, n=200, m=12)
        make = lambda: Ridge(alpha=1.0)                     # noqa: E731
    elif case == "rank_1":
        # as test_linear_solver_divergence_falls_back: a singular Gram, so
        # the host ends on lstsq
        x, y = np.ones((12, 3), np.float32), np.arange(12.0)
        make = lambda: LinearRegression(solver="normal")   # noqa: E731
    else:
        x, y = _cond_1e10()
        make = lambda: LinearRegression(solver="normal",   # noqa: E731
                                        fit_intercept=False)
    xd = from_array(x, (64, x.shape[1]))
    host, dev, rose = _fit_both(monkeypatch, make, xd, y)
    assert rose["solve.host_fallback"] == 1 and rose["solve.device"] == 0
    assert rose["solve.refine_steps"] == 0
    np.testing.assert_array_equal(dev.coef_, host.coef_)
    assert dev.intercept_ == host.intercept_
    assert np.isfinite(dev.coef_).all()


def test_small_systems_never_touch_the_device_solve(monkeypatch):
    from repro.estimators import linear

    def refuse(*args, **kwargs):
        raise AssertionError("the device factor ran below its threshold")

    monkeypatch.setattr(linear, "_normal_factor", refuse)
    m = linear._DEVICE_SOLVE_MIN - 2               # order m + 1, one short
    x, y, _ = regression_data(seed=4, n=3 * m, m=m)
    before = repro.obs.snapshot("solve")
    Ridge(alpha=1.0).fit(from_array(x, (128, 128)), y)
    after = repro.obs.snapshot("solve")
    assert all(after[k] == before.get(k, 0) for k in after)
