"""Faults under ``Ridge.fit``."""

import numpy as np

from bench.tests.faults import _patch

FAULTS = ["ridge_answer_altered", "ridge_half_batch"]


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
