"""Faults under ``KMeans.fit``."""

import jax.numpy as jnp

from bench.tests.faults import _patch

FAULTS = ["kmeans_state_unchanged", "kmeans_half_batch",
          "kmeans_answer_altered"]


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
