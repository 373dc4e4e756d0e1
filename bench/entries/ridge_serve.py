"""A Ridge model served by ``PredictServer``: requests go through
``submit``, micro-batching into the registered buckets, the AOT-warmed
predict plan and the slice back to each request.

The model's weights are drawn from the seed (``w`` normal with variance
``1 / m``, ``b`` normal), as a model's random weights are; the request rows
are the first ``pool_rows`` rows of the configuration's features.  Every
request answered in the window is compared with ``rows @ w + b`` in
float64.

Number: ``pred_err``, the largest ``|p - p_ref|`` over every served row,
over the largest ``|p_ref|`` of the pool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import ridge as ref
from bench.reference.precision import control_mode


def setup(cell):
    import repro.serve as serve
    from repro.estimators import Ridge
    m = cell.config["n_features"]
    w = np.asarray(jax.random.normal(cell.key("serve_w"), (m,), jnp.float32)
                   / np.sqrt(m))
    b = float(jax.random.normal(cell.key("serve_b"), ()))
    model = Ridge(alpha=1.0)
    model.coef_, model.intercept_, model.n_features_in_ = (
        w.astype(np.float64), b, m)
    with jax.profiler.TraceAnnotation("datagen"):
        pool = cell.data().host_rows(cell, cell.params["pool_rows"])
    reg = serve.ModelRegistry()
    reg.register("ridge", model, batch_sizes=tuple(cell.params["sizes"]))
    server = serve.PredictServer(reg)
    # registration compiles each bucket's plan; one request of each size
    # also warms the batch assembly and the slice back
    for size in cell.params["sizes"]:
        f = server.submit("ridge", pool[:size])
        server.pump()
        f.result()
    return {"server": server, "pool": pool, "w": w, "b": b}


def start(state):
    state["server"].start()


def submit(state, payload):
    return state["server"].submit("ridge", payload)


def stop(state):
    state["server"].stop()


def check(cell, state, answers):
    state.pop("server", None)
    want = ref.predict(state["pool"], state["w"], state["b"])
    scale = float(np.abs(want).max())
    err = max(float(np.abs(np.asarray(got, np.float64).ravel()
                           - want[o:o + s]).max()) for o, s, got in answers)
    return {"pred_err": err / scale}


def control(cell, state, answers):
    stated = cell.config["matmul_precision"]
    low = ref.predict(state["pool"], state["w"], state["b"],
                      control_mode(stated))
    return [(o, s, low[o:o + s]) for o, s, _ in answers]
