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
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan
from repro.core.dsarray import DsArray, from_array
from repro.estimators.base import BaseRegressor
from repro.obs import tracing as _tracing
from repro.resilience.guards import NumericalDivergence, require_finite_host

# cond(X) beyond which the squared-cond normal equations lose f32 accuracy
# (cond(G) = cond(X)² ≳ 1/eps_f32 ≈ 1.7e7): fall back to TSQR
_COND_FALLBACK = 3e3


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
        """(XᵀX, Xᵀy, colsums) via one recorded lazy plan: the optimizer
        folds both transposes into ``matmul_ta`` (sparse-native for bcoo)
        and CSE shares the single ``x`` leaf across all three roots."""
        with _tracing.span("fit.stats"):
            y_ds = from_array(jnp.asarray(y, jnp.float32).reshape(-1, 1),
                              (x.block_shape[0], 1))
            xl = x.lazy()
            g = xl.T @ x
            c = xl.T @ y_ds
            s = xl.sum(axis=0)
            g_ds, c_ds, s_ds = plan.compute_multi(g, c, s)
        with _tracing.span("fit.collect"):
            gram = np.asarray(g_ds.collect(), np.float64)
            xty = np.asarray(c_ds.collect(), np.float64).ravel()
            colsum = np.asarray(s_ds.collect(), np.float64).ravel()
        return gram, xty, colsum

    def _solve_normal(self, gram, xty, colsum, n, ysum):
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
        gram = xty = colsum = None
        if solver != "tsqr":
            gram, xty, colsum = self._normal_stats(x, y)
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
            elif solver == "auto":
                solver = "normal"
            if solver == "tsqr":
                self.coef_, self.intercept_ = self._solve_tsqr(x, y)
            else:
                self.coef_, self.intercept_ = self._solve_normal(
                    gram, xty, colsum, n,
                    float(np.asarray(y, np.float64).sum()))
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
