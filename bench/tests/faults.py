"""Faults planted in the program under a cell's timed path.  Each takes a
``monkeypatch`` (or none, in a child process, to plant it for good) and
breaks one thing the comparison with the reference has to catch."""

import jax.numpy as jnp
import numpy as np


class _ForGood:
    @staticmethod
    def setattr(target, name, value):
        setattr(target, name, value)


def _patch(mp, target, name, wrap):
    (mp or _ForGood).setattr(target, name, wrap(getattr(target, name)))


# -- Ridge fit -------------------------------------------------------------

def ridge_answer_altered(mp=None):
    """One coefficient of each fit off by a hundredth of the weights' norm."""
    from repro.estimators.linear import LinearRegression

    def wrap(solve):
        def bad(self, *a):
            coef, icpt = solve(self, *a)
            coef = np.array(coef)
            coef[0] += 0.01 * np.linalg.norm(coef)
            return coef, icpt
        return bad
    _patch(mp, LinearRegression, "_solve_normal", wrap)


def ridge_half_batch(mp=None):
    """The fit takes the first half of the rows and leaves out the rest."""
    from repro.estimators.linear import LinearRegression

    def wrap(validate):
        def bad(self, x, y):
            x, y = validate(self, x, y)
            half = x.shape[0] // 2
            return x[:half], y[:half]
        return bad
    _patch(mp, LinearRegression, "_validate_fit", wrap)


# -- served predict --------------------------------------------------------

def serve_answer_altered(mp=None):
    """Each batch's predictions shifted by a thousandth of their largest."""
    from repro.serve import batching

    def wrap(split):
        def bad(rows, sizes):
            rows = np.asarray(rows)
            return split(rows + 1e-3 * np.abs(rows).max(), sizes)
        return bad
    _patch(mp, batching, "split_rows", wrap)


def serve_half_batch(mp=None):
    """The second half of each batch's rows left out (zeroed) before the
    predict."""
    from repro.serve import batching

    def wrap(assemble):
        def bad(payloads, bucket):
            cut = [np.asarray(p).copy() for p in payloads]
            for p in cut[len(cut) // 2:]:
                p[:] = 0
            if len(cut) == 1:
                cut[0][cut[0].shape[0] // 2:] = 0
            return assemble(cut, bucket)
        return bad
    _patch(mp, batching, "assemble", wrap)


# -- KMeans fit ------------------------------------------------------------

def kmeans_state_unchanged(mp=None):
    """The Lloyd loop returns its starting centres, as if it had run."""
    from repro.algorithms import kmeans

    def wrap(loop):
        def bad(blocks, centers0, row_valid, x_sq, n_cols, tol, max_iter):
            return centers0, jnp.float32(0), max_iter
        return bad
    _patch(mp, kmeans, "_kmeans_run", wrap)


def kmeans_half_batch(mp=None):
    """Only the first half of the rows count as valid."""
    from repro.algorithms.kmeans import KMeans

    def wrap(row_valid):
        def bad(self, x):
            valid = row_valid(self, x)
            flat = valid.reshape(-1)
            keep = jnp.arange(flat.size) < x.shape[0] // 2
            return (flat & keep).reshape(valid.shape)
        return bad
    _patch(mp, KMeans, "_row_valid", wrap)


def kmeans_answer_altered(mp=None):
    """The loop's centres shifted by a hundredth of their largest entry."""
    from repro.algorithms import kmeans

    def wrap(loop):
        def bad(*a, **k):
            c, shift, it = loop(*a, **k)
            return c + 0.01 * jnp.abs(c).max(), shift, it
        return bad
    _patch(mp, kmeans, "_kmeans_run", wrap)

