"""Ridge regression with an unpenalised intercept, and its prediction.

The fit solves the normal equations of ``[x, 1]``:

    [[x'x + alpha I, x'1], [1'x, n]] [w; b] = [x'y; 1'y]

The sums are taken panel by panel on the device at the given precision
mode, then the small system is solved in float64 on the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.precision import matmul


@jax.jit
def _f32_stats(x, y):
    return (matmul(x.T, x, "f32"), matmul(x.T, y[:, None], "f32")[:, 0],
            x.sum(axis=0))


def _stats(x, y, mode):
    if mode == "f32":
        return _f32_stats(x, y)
    return (matmul(x.T, x, mode), matmul(x.T, y[:, None], mode)[:, 0],
            x.sum(axis=0))


def fit(panels, alpha: float, mode: str = "f32"):
    """``(w, b)`` in float64 from ``panels``, an iterable of ``(x, y)``
    device arrays of some rows each."""
    g = xty = s = None
    n, ysum = 0, 0.0
    for x, y in panels:
        gp, cp, sp_ = _stats(x, y, mode)
        g, xty, s = ((gp, cp, sp_) if g is None else
                     (g + gp, xty + cp, s + sp_))
        n += x.shape[0]
        ysum += float(np.asarray(y, np.float64).sum())
    g, xty, s = (np.asarray(v, np.float64) for v in (g, xty, s))
    m = g.shape[0]
    a = np.zeros((m + 1, m + 1))
    a[:m, :m] = g + alpha * np.eye(m)
    a[:m, m] = a[m, :m] = s
    a[m, m] = n
    theta = np.linalg.solve(a, np.concatenate([xty, [ysum]]))
    return theta[:m], float(theta[m])


def predict(rows: np.ndarray, w: np.ndarray, b: float,
            mode: str = "f64") -> np.ndarray:
    """``rows @ w + b``: in float64 on the host, or on the device at a
    precision mode (the control)."""
    if mode == "f64":
        return np.asarray(rows, np.float64) @ np.asarray(w, np.float64) + b
    p = matmul(jnp.asarray(rows, jnp.float32),
               jnp.asarray(w, jnp.float32)[:, None], mode)[:, 0]
    return np.asarray(p + jnp.float32(b), np.float64)
