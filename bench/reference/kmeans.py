"""k-means with k-means++ seeding on a sparse matrix, as the program's
``KMeans.fit`` defines it: D^2 sampling from ``numpy.random.default_rng(seed)``
(the first centre ``rng.integers(n)``, each next one ``rng.choice(n, p=d2 /
sum(d2))``), then Lloyd iterations from those centres until the centres'
shift is not above ``tol`` or ``max_iter`` iterations are done.  Distances
are ``|x|^2 - 2 x.c + |c|^2``, ties go to the lower centre, and an empty
centre stays where it was.

The stored entries are kept as COO arrays on the device and every
contraction is a segment sum over them, at ``dtype``: float32 for the
reference, bfloat16 for its control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("n",))
def _row_dots(rows, cols, vals, c, n: int):
    """``x @ c`` for one dense centre ``c``: ``(n,)``."""
    return jax.ops.segment_sum(vals * c[cols], rows, n,
                               indices_are_sorted=True)


@functools.partial(jax.jit, static_argnames=("n",))
def _d2(rows, cols, vals, x_sq, c, n: int):
    return jnp.maximum(x_sq - 2 * _row_dots(rows, cols, vals, c, n)
                       + jnp.sum(c * c), 0)


@functools.partial(jax.jit, static_argnames=("n", "m"))
def _lloyd(rows, cols, vals, x_sq, centres, n: int, m: int):
    k = centres.shape[0]
    dots = jax.lax.map(lambda c: _row_dots(rows, cols, vals, c, n), centres)
    dist = x_sq[None, :] - 2 * dots + jnp.sum(centres * centres, 1)[:, None]
    labels = jnp.argmin(dist, axis=0)
    sums = jax.ops.segment_sum(vals, labels[rows] * m + cols, k * m)
    counts = jnp.zeros(k, vals.dtype).at[labels].add(1)
    new = jnp.where(counts[:, None] > 0,
                    sums.reshape(k, m) / jnp.maximum(counts, 1)[:, None],
                    centres)
    return new, jnp.sqrt(jnp.sum((new - centres) ** 2))


def fit(mat, k: int, max_iter: int, tol: float, seed: int,
        dtype=jnp.float32):
    """``(centres, iterations)``: float32 centres ``(k, m)`` as a host
    array, computed at ``dtype``."""
    n, m = mat.shape
    rows = jnp.asarray(np.repeat(np.arange(n, dtype=np.int32),
                                 np.diff(mat.indptr)))
    cols = jnp.asarray(mat.indices.astype(np.int32))
    vals = jnp.asarray(mat.data).astype(dtype)
    x_sq = jax.ops.segment_sum(vals * vals, rows, n, indices_are_sorted=True)

    def row(i):
        lo, hi = mat.indptr[i], mat.indptr[i + 1]
        r = np.zeros(m, np.float32)
        r[mat.indices[lo:hi]] = mat.data[lo:hi]
        return jnp.asarray(r).astype(dtype)

    rng = np.random.default_rng(seed)
    centres = [row(int(rng.integers(n)))]
    d2 = _d2(rows, cols, vals, x_sq, centres[0], n=n)
    for _ in range(1, k):
        d = np.maximum(np.asarray(d2, np.float64), 0.0)
        tot = d.sum()
        p = d / tot if tot > 0 else np.full(n, 1.0 / n)
        centres.append(row(int(rng.choice(n, p=p))))
        d2 = jnp.minimum(d2, _d2(rows, cols, vals, x_sq, centres[-1], n=n))
    c = jnp.stack(centres)
    it = 0
    while it < max_iter:
        c, shift = _lloyd(rows, cols, vals, x_sq, c, n=n, m=m)
        it += 1
        if not float(shift) > tol:
            break
    return np.asarray(c.astype(jnp.float32)), it
