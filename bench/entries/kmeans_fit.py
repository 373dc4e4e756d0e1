"""``KMeans(n_clusters, max_iter, tol, seed).fit(x)`` on a BCOO-blocked
sparse array resident on the device.

The comparison covers the k-means++ start, the sparse contractions and
the Lloyd step: every fit of the window is held against the reference's
centres, computed from the benchmark's own copy of the matrix.

Numbers: ``centre_err``, the largest ``|C - C_ref|_F / |C_ref|_F`` over the
fits; ``iter_gap``, the largest difference in the number of iterations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import kmeans as ref


def setup(cell):
    from repro.core.sparse import from_scipy
    c, p = cell.config, cell.params
    with jax.profiler.TraceAnnotation("datagen"):
        mat, _ = cell.data().make(cell)
        x = from_scipy(mat, (c["block_rows"], c["n_features"]),
                       nse=c["block_nse"])
        jax.block_until_ready((x.blocks.data, x.blocks.indices))
    state = {"x": x, "mat": mat, "k": p["n_clusters"],
             "max_iter": p["max_iter"], "tol": float(p["tol"]),
             "seed": cell.int_seed("kmeans")}
    call(state)
    return state


def call(state):
    from repro.algorithms import KMeans
    km = KMeans(n_clusters=state["k"], max_iter=state["max_iter"],
                tol=state["tol"], seed=state["seed"]).fit(state["x"])
    return jax.block_until_ready(km.centers_), km.n_iter_


def reference(state, dtype=jnp.float32):
    state.pop("x", None)
    return ref.fit(state["mat"], state["k"], state["max_iter"], state["tol"],
                   state["seed"], dtype)


def check(cell, state, answers):
    c_ref, it_ref = reference(state)
    norm = np.linalg.norm(c_ref)
    return {
        "centre_err": max(float(np.linalg.norm(np.asarray(c, np.float64)
                                               - c_ref) / norm)
                          for c, _ in answers),
        "iter_gap": max(abs(it - it_ref) for _, it in answers),
    }


def control(cell, state, answers):
    return [reference(state, jnp.bfloat16)] * len(answers)
