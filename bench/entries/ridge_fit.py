"""``Ridge(alpha).fit(x, y)`` on a dense array resident on the device.

The comparison covers the Gram kernel, the other sums of the fit's plan
and the driver's host solve: every fit of the window is held against the
reference's ``(w, b)``, computed panel by panel from the same seed.

Number: ``coef_err``, the largest ``|w - w_ref| / |w_ref|`` over the fits.
The intercept is not compared: at JAX's default precision its gap read up
to 5.4e-7 in sound runs on the chip and 1.1e-6 under a bfloat16 control,
too close for any limit to part them.
"""

from __future__ import annotations

import jax
import numpy as np

from bench.reference import ridge as ref
from bench.reference.precision import control_mode


def setup(cell):
    from repro.core.dsarray import from_array
    c = cell.config
    with jax.profiler.TraceAnnotation("datagen"):
        xd, y = cell.data().make(
            cell, lambda x: from_array(x, tuple(c["block"])))
        jax.block_until_ready(xd.blocks)
    state = {"x": xd, "y": np.asarray(y), "alpha": float(cell.params["alpha"])}
    call(state)
    return state


def call(state):
    from repro.estimators import Ridge
    r = Ridge(alpha=state["alpha"]).fit(state["x"], state["y"])
    return jax.block_until_ready(r.coef_), float(r.intercept_)


def _panels(cell, y):
    data, rows = cell.data(), cell.config["panel_rows"]
    for p in range(data.panels(cell)):
        x = data.panel(cell, p)
        yield x, jax.numpy.asarray(y[p * rows: p * rows + x.shape[0]])


def reference(cell, state, mode="f32"):
    state.pop("x", None)
    return ref.fit(_panels(cell, state["y"]), state["alpha"], mode)


def check(cell, state, answers):
    w_ref, _ = reference(cell, state)
    return {"coef_err": max(
        float(np.linalg.norm(np.asarray(w, np.float64) - w_ref)
              / np.linalg.norm(w_ref)) for w, _ in answers)}


def control(cell, state, answers):
    stated = cell.config["matmul_precision"]
    return [reference(cell, state, control_mode(stated))] * len(answers)
