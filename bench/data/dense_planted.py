"""Dense features with labels from a planted linear model, shaped like
LIBSVM ``epsilon``.

The features are standard normal, made on the device in row panels of
``panel_rows``: panel ``p`` is drawn from its own key, so the reference can
make any panel again alone.  The labels are ``sign(x @ w + noise * e)`` with
``w`` and ``e`` standard normal, as the smoke run made them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# the sizes a CPU test holds
TINY = dict(n_rows=3000, n_features=300, block=[1024, 128], panel_rows=1024)


def _panel(key, p, rows: int, m: int):
    return jax.random.normal(jax.random.fold_in(key, p), (rows, m),
                             jnp.float32)


def make(cell, wrap=lambda x: x):
    """``(wrap(x), y)`` in one jitted call: the ``(n, m)`` float32 features
    and the ``(n,)`` labels in {-1, +1}.  ``wrap`` takes the features into
    the program inside the same call, so the features never exist on the
    device beside the program's copy of them."""
    c = cell.config
    n, m, rows = c["n_rows"], c["n_features"], c["panel_rows"]
    panels = -(-n // rows)

    def build(kx, kw, kn):
        w = jax.random.normal(kw, (m,), jnp.float32)

        def one(p):
            # a panel at a time: the whole array's random bits, or its bf16
            # parts under HIGHEST, would not fit beside it
            xp = _panel(kx, p, rows, m)
            return xp, jnp.matmul(xp, w, precision=jax.lax.Precision.HIGHEST)

        x, score = jax.lax.map(one, jnp.arange(panels))
        x, score = x.reshape(panels * rows, m)[:n], score.reshape(-1)[:n]
        e = jax.random.normal(kn, (n,), jnp.float32)
        y = jnp.sign(score + float(c["label_noise"]) * e)
        return wrap(x), jnp.where(y == 0, 1.0, y)

    return jax.jit(build)(cell.key("x"), cell.key("w"), cell.key("noise"))


_panel_jit = jax.jit(_panel, static_argnames=("rows", "m"))


def panels(cell):
    """The number of row panels the features are made in."""
    return -(-cell.config["n_rows"] // cell.config["panel_rows"])


def panel(cell, p: int):
    """Rows ``[p * panel_rows, (p + 1) * panel_rows)`` of the features (the
    last panel cut at ``n_rows``), made again from the seed."""
    c = cell.config
    rows, n = c["panel_rows"], c["n_rows"]
    x = _panel_jit(cell.key("x"), p, rows=rows, m=c["n_features"])
    return x[: max(0, min(rows, n - p * rows))]


def host_rows(cell, count: int) -> np.ndarray:
    """The first ``count`` rows (at most one panel) on the host."""
    return np.asarray(panel(cell, 0)[:count])
