"""The precision modes of the plain references, the control each stated
precision steps down to, and the rcv1-shaped data maker's row scaling."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib.cell import ROOT
from bench.reference import precision
from bench.tests import tiny


def _err(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / np.linalg.norm(want))


def test_bf16x3_lies_between_one_pass_and_float32():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 512), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (512, 64), jnp.float32)
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    one_pass = jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    e32 = _err(precision.matmul(a, b, "f32"), want)
    e3 = _err(precision.matmul(a, b, "bf16x3"), want)
    assert e32 < e3 < _err(one_pass, want) / 30


def test_control_mode_steps_down_or_refuses():
    assert precision.control_mode("highest") == "bf16x3"
    with pytest.raises(ValueError):
        precision.control_mode("default")
    with pytest.raises(ValueError):
        precision.matmul(jnp.ones((2, 2)), jnp.ones((2, 2)), "bf16")


def test_each_stated_precision_has_a_control():
    for c in json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        if "matmul_precision" in cfg:
            assert precision.control_mode(cfg["matmul_precision"])


@pytest.mark.parametrize("unit", [False, True])
def test_sparse_text_row_scaling(unit):
    c = tiny.cell("rcv1.kmeans")
    c.config["unit_norm_rows"] = unit
    mat, y = c.data().make(c)
    c.config["unit_norm_rows"] = not unit
    other, _ = c.data().make(c)
    assert (mat.indptr == other.indptr).all()
    assert (mat.indices == other.indices).all()
    norms = np.sqrt(np.asarray(mat.multiply(mat).sum(axis=1))).ravel()
    if unit:
        np.testing.assert_allclose(norms, 1.0, rtol=1e-6)
    else:
        assert np.abs(norms - 1).max() > 0.5
    assert set(np.unique(y)) == {-1, 1}
