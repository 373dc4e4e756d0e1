"""``refine_steps.fit``: the reader on hand-made counters, and on a traced
run of the Ridge cell at tiny size with the device solve's order threshold
lowered to the tiny Gram's."""

import pytest

from bench.lib import runner
from bench.metrics import refine_steps
from bench.tests import tiny


def _ctx(counters):
    return runner.Context(cell=None, state={}, reduction=None,
                          counters=counters, spans=[], calls=1,
                          device_kind="cpu")


def test_reader_on_hand_made_counters():
    ctx = _ctx({"solve.device": 4, "solve.refine_steps": 6,
                "solve.host_fallback": 0})
    assert refine_steps.read(ctx, {}) == pytest.approx(1.5)
    # no device solve in the window, or a program without the counters
    assert refine_steps.read(_ctx({"solve.device": 0,
                                   "solve.refine_steps": 0}), {}) is None
    assert refine_steps.read(_ctx({}), {}) is None


def test_traced_tiny_ridge_reports_refine_steps(monkeypatch, tmp_path):
    from repro.estimators import linear
    monkeypatch.setattr(linear, "_DEVICE_SOLVE_MIN", 2)
    c = tiny.cell("epsilon.ridge")
    res = tiny.run(c, trace=True, tmp=tmp_path)
    assert res["correct"] is True, res["checks"]
    steps = res["metrics"]["refine_steps.fit"]
    assert steps["unit"] == "steps" and 1 <= steps["value"] <= 3
