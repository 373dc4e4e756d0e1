"""Device idle time charged to the program's spans: the innermost-span
rule on hand-made traces, the offset that puts the program's buffer on the
reduction's clock, each new reader on a hand-made context, and the readers
on a traced run of each cell at tiny size."""

import pytest

from bench.lib import attribution as at, runner, trace as tr
from bench.metrics import (driver_idle, plan_idle, serve_idle,
                           serve_queue_ms, solve_ms)
from bench.tests import tiny


def _reduction(busy, host, window=(0, 1000)):
    ops = [("op", s, e) for s, e in busy]
    return tr.Reduction(window, [tr.Device("/device:TPU:0", ops, [],
                                           tr.union(busy))], host)


def _ctx(red, spans, calls=1):
    return runner.Context(cell=None, state={}, reduction=red, counters={},
                          spans=spans, calls=calls, device_kind="cpu")


def _buffered(name, start_ns, end_ns, parent=None, **args):
    """A ``repro.obs`` buffer event: microseconds, real-time clock."""
    return {"name": name, "ph": "X", "ts": start_ns / 1e3,
            "dur": (end_ns - start_ns) / 1e3, "parent": parent,
            "args": args}


def test_idle_splits_by_innermost_span_then_annotation():
    # busy 0-100 and 900-1000: idle 100-900
    red = _reduction([(0, 100), (900, 1000)], [("fit", 0, 700)])
    spans = [("fit.solve", 150, 600),           # thread 1
             ("plan.build", 200, 300),          # thread 1, nested
             ("serve.dispatch", 250, 400)]      # thread 2, starts later
    shares = at.idle_by_label(red, spans)
    ns = {k: round(v * 1000) for k, v in shares.items()}
    assert ns == {"fit": 50 + 100,              # 100-150, 600-700
                  "fit.solve": 50 + 200,        # 150-200, 400-600
                  "plan.build": 50,             # 200-250
                  "serve.dispatch": 150,        # 250-400: latest started
                  "host idle": 200}             # 700-900
    assert sum(shares.values()) == pytest.approx(red.idle_share())


def test_idle_shares_sum_to_the_idle_share_on_any_trace():
    busy = [(s, s + 7) for s in range(0, 990, 37)]
    host = [("fit", s, s + 50) for s in range(0, 1000, 120)]
    spans = [(f"plan.x{s % 3}", s, s + 90) for s in range(5, 990, 45)]
    shares = at.idle_by_label(_reduction(busy, host), spans)
    red = _reduction(busy, host)
    assert sum(shares.values()) == pytest.approx(red.idle_share(), abs=1e-12)
    assert sum(at.idle_by_label(red, []).values()) == pytest.approx(
        red.idle_share(), abs=1e-12)


def test_gaps_take_the_label_holding_most_of_them():
    red = _reduction([(0, 100), (600, 650), (1090, 1100)],
                     [("fit", 100, 400), ("submit", 700, 100)],
                     window=(0, 1100))
    red.host = [("fit", 100, 400), ("submit", 700, 800)]
    # no program spans: the annotations label the gaps, as before
    assert at.idle_gaps(red, []) == red.idle_gaps()
    spans = [("fit.solve", 120, 500), ("plan.build", 700, 720)]
    gaps = at.idle_gaps(red, spans)
    assert gaps[0] == ["fit.solve", pytest.approx(500e-9)]    # 100-600
    assert gaps[1] == ["submit", pytest.approx(440e-9)]       # 650-1090


def test_program_spans_move_onto_the_reduction_clock():
    # a real-time stamp, ns; the buffer keeps microseconds as floats, a
    # quarter of one apart at this size, so the test moves by whole ones
    base, us = 1_800_000_000_000_000_000, 1000
    red = _reduction([(0, 10)], [("wait", 5 * us, 40 * us),
                                 ("fit", 50 * us, 900 * us)])
    spans = [_buffered("fit.stats", base + 60 * us, base + 400 * us),
             _buffered("plan.build", base + 70 * us, base + 90 * us,
                       "fit.stats"),
             _buffered("serve.queue", base, base + 300 * us),
             _buffered("other.x", base + us, base + 2 * us)]
    got = at.program_spans(_ctx(red, spans))
    # the first span opens where the first call's annotation opens
    assert got == [("fit.stats", 50 * us, 390 * us),
                   ("plan.build", 60 * us, 80 * us)]
    # a buffer without ``parent`` is on another clock: not read
    for e in spans:
        del e["parent"]
    assert at.program_spans(_ctx(red, spans)) is None
    assert at.program_spans(_ctx(red, [])) is None


def test_idle_readers_on_a_hand_made_context():
    red = _reduction([(0, 100), (900, 1000)], [("fit", 100, 800)])
    t = 5_000_000
    spans = [_buffered("fit.validate", t, t + 100),
             _buffered("fit.solve", t + 100, t + 500),
             _buffered("plan.build", t + 200, t + 300, "fit.solve"),
             _buffered("resilience.rung", t + 500, t + 600),
             _buffered("serve.slice", t + 600, t + 700)]
    ctx = _ctx(red, spans)
    # idle 100-900 on a 1000 ns window; fit.* 100-200 and 300-600
    assert driver_idle.read(ctx, {}) == pytest.approx(40.0)
    assert plan_idle.read(ctx, {}) == pytest.approx(20.0)
    assert serve_idle.read(ctx, {}) == pytest.approx(10.0)
    for reader in (driver_idle, plan_idle, serve_idle):
        assert reader.read(_ctx(red, []), {}) is None
        assert reader.read(_ctx(None, spans), {}) is None


def test_duration_readers_on_a_hand_made_context():
    spans = [_buffered("fit.solve", 0, 2_000_000),
             _buffered("fit.solve", 0, 4_000_000),
             _buffered("serve.queue", 0, 1_500_000, request=0, batch=0),
             _buffered("serve.queue", 0, 500_000, request=1, batch=0)]
    ctx = _ctx(None, spans, calls=2)
    assert solve_ms.read(ctx, {}) == pytest.approx(3.0)
    assert serve_queue_ms.read(ctx, {}) == pytest.approx(1.0)
    assert solve_ms.read(_ctx(None, []), {}) is None
    assert serve_queue_ms.read(_ctx(None, []), {}) is None


@pytest.mark.parametrize("name, wanted", [
    ("epsilon.ridge", {"driver_idle.fit", "plan_idle.fit", "solve_ms.fit"}),
    ("epsilon.serve", {"serve_idle.serve", "plan_idle.serve",
                       "serve_queue_ms"}),
])
def test_traced_tiny_cell_reports_the_span_metrics(name, wanted, tmp_path):
    c = tiny.cell(name)
    res = tiny.run(c, trace=True, tmp=tmp_path)
    metrics = res["metrics"]
    assert wanted <= set(metrics), metrics
    for m in wanted:
        assert metrics[m]["value"] >= 0
    idle = metrics.get("idle_share.fit", metrics.get("idle_share.serve"))
    charged = sum(metrics[m]["value"] for m in wanted if "idle" in m)
    assert charged <= idle["value"] + 1e-9


def test_offset_agrees_with_the_profilers_copy_of_the_spans(tmp_path):
    import glob
    import os

    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from repro import obs
    from repro.core.dsarray import from_array
    from repro.estimators import Ridge

    rng = np.random.default_rng(0)
    x = from_array(rng.normal(size=(256, 16)).astype(np.float32), (64, 16))
    y = rng.normal(size=256)
    Ridge().fit(x, y)
    obs.clear()
    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("fit"):
                    Ridge().fit(x, y)
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    red = tr.load(str(tmp_path))
    got = at.program_spans(_ctx(red, obs.events()))
    pd = ProfileData.from_file(glob.glob(
        os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[0])
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    want = sorted((int(ev.start_ns), ev.name) for line in host.lines
                  for ev in line.events if ev.name.startswith(at.PREFIXES))
    got = sorted(got, key=lambda g: g[1])
    assert [n for _, n in want] == [n for n, _, _ in got]
    # one offset for the whole window: each span sits where the profiler's
    # own copy of it does, moved by how far the window's first span opened
    # after the first call's annotation (the offset takes that as 0)
    first_call = min(s for n, s, _ in red.host if n == "fit")
    lag = want[0][0] - first_call
    assert 0 <= lag < 10_000_000
    for (start, name), (_, s, _) in zip(want, got):
        assert abs(s + lag - start) < 1_000_000, name
