"""Distributed linear models: LinearRegression / Ridge on ds-arrays.

Fit goes through the **distributed normal equations**: the Gram matrix
``XᵀX`` and moment vector ``Xᵀy`` are recorded as ONE lazy plan —
``x.lazy().T @ x`` folds to the transpose-absorbed GEMM (``matmul_ta``) and
hash-consing shares the ``x`` leaf between the two products — then the
small ``(m+1, m+1)`` system solves host-side.  For BCOO-blocked ``x`` the
sparse operand rides the sparse-lhs ``bcoo_dot_general`` path: ``Xᵀy`` and
the column sums are fully sparse-native, and ``XᵀX`` streams the stored
entries on the left (only the rhs copy takes its dense form — jax has no
sp×sp contraction; same policy as ``core.structural.gram``).  The intercept
is carried as an augmented row/column built from ``x.sum(axis=0)``
(sparse-native), NOT by centering, so sparse inputs stay sparse.

The system is symmetric positive definite, so from order
``_DEVICE_SOLVE_MIN`` up it is assembled and Cholesky-factored in float32
on the device (``_normal_factor``, dispatched before the Gram is copied to
the host), and the host refines that first solution in float64 against the
collected Gram until its normwise backward error reaches the float64 LU's
own level: the answer is the float64 solve's.  A non-finite factor, or a
refinement that does not get there, falls back to the host LU.

Ill-conditioned tall-skinny inputs: the normal equations square the
condition number, so when ``alpha == 0`` and the Gram's spectrum says
``cond(X) ≳ 1/√eps`` the fit falls back to the **TSQR** factorization
(``algorithms.linalg.tsqr``: vmapped per-block QR + an R-merge reduction
tree) and solves ``R θ = Qᵀ y`` — numerically safe for the f32 block
tensors.  Ridge (``alpha > 0``) regularizes the Gram directly and keeps the
one-plan path.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan
from repro.core.dsarray import DsArray, from_array
from repro.estimators.base import BaseRegressor
from repro.obs import metrics as _metrics
from repro.obs import tracing as _tracing
from repro.resilience.guards import NumericalDivergence, require_finite_host

# cond(X) beyond which the squared-cond normal equations lose f32 accuracy
# (cond(G) = cond(X)² ≳ 1/eps_f32 ≈ 1.7e7): fall back to TSQR
_COND_FALLBACK = 3e3
# order of the normal-equations system from which the device factor plus
# host refinement beats the host LU (the crossover measured on a v5e)
_DEVICE_SOLVE_MIN = 512
# cond(XᵀX) beyond which float32-factor refinement converges slowly or not
# at all (it contracts the error by about cond · eps_f32 a step)
_REFINE_COND_MAX = 1e5
_REFINE_STEPS = 8

# normal-equations solves finished on the device path, their refinement
# steps (a sum), and device attempts that ended on the host LU
_SOLVE = _metrics.CounterGroup(
    "solve", ("device", "refine_steps", "host_fallback"))


@functools.partial(jax.jit, static_argnames=("intercept",))
def _normal_factor(gram, xty, colsum, consts, intercept):
    """The normal-equations system ``[[G + αI, s], [sᵀ, n]]`` (or ``G + αI``
    without the intercept), assembled in float32 and padded with identity
    to a multiple of 128; its lower Cholesky factor, the first solution,
    ``‖A‖∞`` and whether the factor and solution are finite.  ``consts``
    is ``(n, alpha, Σy)``."""
    m = gram.shape[0]
    k = m + int(intercept)
    p = -(-k // 128) * 128
    n, alpha, ysum = consts[0], consts[1], consts[2]
    a = jnp.eye(p, dtype=jnp.float32).at[:m, :m].set(
        gram + alpha * jnp.eye(m, dtype=jnp.float32))
    b = jnp.zeros(p, jnp.float32).at[:m].set(xty.ravel())
    if intercept:
        s = colsum.ravel()
        a = a.at[:m, m].set(s).at[m, :m].set(s).at[m, m].set(n)
        b = b.at[m].set(ysum)
    chol = jax.lax.linalg.cholesky(a)
    theta = jax.scipy.linalg.cho_solve((chol, True), b)
    finite = jnp.isfinite(chol).all() & jnp.isfinite(theta).all()
    return chol, theta[:k], jnp.abs(a[:k]).sum(axis=1).max(), finite


@jax.jit
def _factor_solve(chol, r):
    """``A⁻¹ r`` through the Cholesky factor of :func:`_normal_factor`."""
    return jax.scipy.linalg.cho_solve((chol, True), r)


@dataclasses.dataclass
class LinearRegression(BaseRegressor):
    """Ordinary least squares ``y = x @ coef_ + intercept_`` on ds-arrays.

    ``solver``: ``"auto"`` (normal equations, TSQR fallback when the Gram
    is ill-conditioned and ``alpha == 0``), ``"normal"``, or ``"tsqr"``
    (dense inputs only — QR factors are dense whatever the input).
    """

    fit_intercept: bool = True
    alpha: float = 0.0
    solver: str = "auto"

    coef_: Optional[np.ndarray] = None
    intercept_: float = 0.0
    n_features_in_: int = 0
    solver_used_: str = ""

    def _normal_stats(self, x: DsArray, y: np.ndarray):
        """(XᵀX, Xᵀy, colsums) on the device via one recorded lazy plan:
        the optimizer folds both transposes into ``matmul_ta``
        (sparse-native for bcoo) and CSE shares the single ``x`` leaf
        across all three roots."""
        with _tracing.span("fit.stats"):
            y_ds = from_array(jnp.asarray(y, jnp.float32).reshape(-1, 1),
                              (x.block_shape[0], 1))
            xl = x.lazy()
            g = xl.T @ x
            c = xl.T @ y_ds
            s = xl.sum(axis=0)
            return tuple(d.collect() for d in plan.compute_multi(g, c, s))

    def _refine(self, gram, xty, colsum, n, ysum, factor):
        """Refine the device factor's first solution in float64 until the
        normwise backward error ``‖r‖∞ / (‖A‖∞‖θ‖∞ + ‖b‖∞)`` is at most
        ``k·2⁻⁵³`` for the order-``k`` system, the float64 LU's own bound.
        The residual is formed from the collected Gram, column sums, ``n``
        and ``alpha`` as block terms, never from a dense augmented matrix;
        each correction runs through the float32 factor on the device.  Returns ``(θ, steps)``, θ None where the error
        stops falling or ``_REFINE_STEPS`` corrections do not reach it."""
        chol, theta, a_norm, finite = factor
        theta, a_norm, finite = jax.device_get((theta, a_norm, finite))
        if not finite:
            return None, 0
        theta = theta.astype(np.float64)
        m, k = gram.shape[0], theta.size
        b_norm = max(np.abs(xty).max(initial=0.0),
                     abs(ysum) if k > m else 0.0)
        tol = k * 2.0 ** -53
        r_pad = np.zeros(chol.shape[0], np.float32)
        last = np.inf
        for step in range(_REFINE_STEPS + 1):
            with _tracing.span("fit.refine"):
                w = theta[:m]
                r = xty - (gram @ w + self.alpha * w)
                if k > m:
                    r = np.append(r - colsum * theta[m],
                                  ysum - (colsum @ w + n * theta[m]))
                r_max = np.abs(r).max()
                err = r_max / (float(a_norm) * np.abs(theta).max() + b_norm)
                if err <= tol:
                    return theta, step
                if not err < last or step == _REFINE_STEPS:
                    return None, step
                last = err
                # scaled to ‖r‖∞ = 1, so float32 neither under- nor overflows
                r_pad[:k] = r / r_max
                theta = theta + r_max * np.asarray(
                    _factor_solve(chol, r_pad), np.float64)[:k]

    def _solve_normal(self, gram, xty, colsum, n, ysum, factor=None):
        if factor is not None:
            theta, steps = self._refine(gram, xty, colsum, n, ysum, factor)
            if theta is not None:
                _SOLVE.inc("device")
                _SOLVE.inc("refine_steps", steps)
                return self._split(theta, gram.shape[0])
            _SOLVE.inc("host_fallback")
        m = gram.shape[0]
        if self.fit_intercept:
            a = np.zeros((m + 1, m + 1))
            a[:m, :m] = gram
            a[:m, m] = colsum
            a[m, :m] = colsum
            a[m, m] = n
            b = np.concatenate([xty, [ysum]])
            reg = np.eye(m + 1) * self.alpha
            reg[m, m] = 0.0                      # never penalize the intercept
        else:
            a, b, reg = gram, xty, np.eye(m) * self.alpha
        try:
            theta = require_finite_host(np.linalg.solve(a + reg, b),
                                        "normal-equations solution")
        except (np.linalg.LinAlgError, NumericalDivergence):
            # rank-deficient Gram (all-zero feature columns are routine in
            # sparse text data): the min-norm lstsq solution, like sklearn
            theta = np.linalg.lstsq(a + reg, b, rcond=None)[0]
        return self._split(theta, m)

    def _split(self, theta, m):
        if self.fit_intercept:
            return theta[:m], float(theta[m])
        return theta, 0.0

    def _solve_tsqr(self, x: DsArray, y: np.ndarray):
        """QR path for ill-conditioned tall-skinny inputs: cond(R) ==
        cond(X), no squaring.  The intercept comes from centering (dense
        path only); ``alpha > 0`` solves the REGULARIZED least squares by
        factoring the row-augmented system ``[X; √α·I]`` with zero-extended
        targets — QR of the augmented matrix is the textbook
        squaring-free ridge, so an explicit ``solver="tsqr"`` never drops
        the requested penalty."""
        from repro.algorithms.linalg import tsqr
        from repro.core.dsarray import concat_rows, from_array as _fa
        if x.is_sparse:
            # QR factors are dense whatever the input; centering below
            # would densify anyway — callers on sparse data keep the
            # (ridge-regularized) normal equations instead
            raise ValueError("tsqr solver supports dense inputs only")
        n, m = x.shape
        if n < m:
            raise ValueError("tsqr solver needs a tall (n >= m) input")
        if x.block_shape[0] < m:
            # tsqr's leaf QR needs m <= block rows: re-block (block-native)
            x = x.rechunk((min(n, max(x.block_shape[0], m)),
                           x.block_shape[1]))
        yv = np.asarray(y, np.float64)
        if self.fit_intercept:
            from repro.algorithms.linalg import _broadcast_rows
            mean_row = x.mean(axis=0)
            xc = x - _broadcast_rows(mean_row, x.shape[0], x.block_shape[0])
            ym = yv.mean()
            yc = yv - ym
        else:
            xc, yc, ym = x, yv, 0.0
        if self.alpha > 0.0:
            ridge_rows = _fa(np.sqrt(self.alpha) * np.eye(m, dtype=np.float32),
                             xc.block_shape)
            xc = concat_rows([xc, ridge_rows])
            yc = np.concatenate([yc, np.zeros(m)])
        q, r = tsqr(xc)
        qty = np.asarray(q, np.float64).T @ yc
        try:
            coef = require_finite_host(
                np.linalg.solve(np.asarray(r, np.float64), qty),
                "tsqr R-solve solution")
        except (np.linalg.LinAlgError, NumericalDivergence):
            # singular R (exactly collinear/zero columns): min-norm solve
            coef = np.linalg.lstsq(np.asarray(r, np.float64), qty,
                                   rcond=None)[0]
        if self.fit_intercept:
            mean = np.asarray(mean_row.collect(), np.float64).ravel()
            return coef, float(ym - mean @ coef)
        return coef, 0.0

    def fit(self, x, y) -> "LinearRegression":
        with self._driver_scope():
            return self._fit(x, y)

    def _fit(self, x, y) -> "LinearRegression":
        with _tracing.span("fit.validate"):
            x, y = self._validate_fit(x, y)
        n, m = x.shape
        self.n_features_in_ = m
        if self.solver not in ("auto", "normal", "tsqr"):
            raise ValueError(f"unknown solver {self.solver!r}")
        solver = self.solver
        gram = xty = colsum = factor = None
        ysum = float(np.asarray(y, np.float64).sum())
        if solver != "tsqr":
            stats = self._normal_stats(x, y)
            if m + self.fit_intercept >= _DEVICE_SOLVE_MIN:
                # the chip factors while the host copies the Gram; the
                # factor is dropped where the spectrum below rules it out
                with _tracing.span("fit.factor"):
                    factor = _normal_factor(
                        *stats, np.array([n, self.alpha, ysum], np.float32),
                        intercept=bool(self.fit_intercept))
            with _tracing.span("fit.collect"):
                gram, xty, colsum = (np.asarray(d, np.float64)
                                     for d in stats)
                xty, colsum = xty.ravel(), colsum.ravel()
        with _tracing.span("fit.solve"):
            if solver == "auto" and self.alpha == 0.0 and not x.is_sparse \
                    and n >= m:
                ev = np.linalg.eigvalsh(gram)
                lo, hi = max(float(ev[0]), 0.0), float(ev[-1])
                # cond(X) = sqrt(cond(XᵀX)); degenerate spectrum → fallback
                if lo <= 0 or np.sqrt(hi / lo) > _COND_FALLBACK:
                    solver = "tsqr"
                else:
                    solver = "normal"
                    if hi / lo > _REFINE_COND_MAX:
                        factor = None
            elif solver == "auto":
                solver = "normal"
            if solver == "tsqr":
                self.coef_, self.intercept_ = self._solve_tsqr(x, y)
            else:
                self.coef_, self.intercept_ = self._solve_normal(
                    gram, xty, colsum, n, ysum, factor)
        self.solver_used_ = solver
        return self

    def _weights_ds(self, block_cols: int) -> DsArray:
        """``coef_`` as a device-pinned ``(m, 1)`` ds-array, cached per
        column blocking AND per fitted-coefficient identity: the serving
        hot path re-records predict per request batch, and reusing ONE leaf
        array keeps the plan's alias structure stable (``_OPT_CACHE`` hits)
        and skips a host->device transfer per request.  A refit (new
        ``coef_`` object) naturally invalidates the cached entry."""
        cache = self.__dict__.setdefault("_predict_cache", {})
        key = (int(block_cols), id(self.coef_))
        w = cache.get(key)
        if w is None:
            cache.clear()                    # one fit, one blocking at a time
            w = from_array(jnp.asarray(self.coef_, jnp.float32).reshape(-1, 1),
                           (block_cols, 1))
            jax.block_until_ready(w.blocks)
            cache[key] = w
        return w

    def _predict_expr(self, xl):
        """``x @ coef_ + intercept_`` recorded on the lazy input: the
        matmul is the sparse-native ``sp @ dense`` path for bcoo inputs,
        and the whole expression is one cacheable plan (the serve layer's
        AOT target)."""
        out = xl @ self._weights_ds(xl.block_shape[1])
        if self.intercept_ != 0.0:
            out = out + float(self.intercept_)
        return out

    def predict(self, x) -> DsArray:
        """``x @ coef_ + intercept_`` as a new ``(n, 1)`` ds-array,
        computed through the SAME recorded plan the serving layer caches
        (``_predict_expr``), so direct and served predictions are
        bit-identical and repeat predicts hit the structural plan cache."""
        self._check_fitted("coef_")
        with self._driver_scope():
            x = self._validate_x(x)
            return plan.compute(self._predict_expr(x.lazy()))


@dataclasses.dataclass
class Ridge(LinearRegression):
    """L2-regularized linear regression: the Gram gets ``alpha`` added to
    its diagonal (intercept unpenalized), which also keeps the normal
    equations well-posed on rank-deficient inputs — so Ridge never needs
    the TSQR fallback."""

    alpha: float = 1.0
