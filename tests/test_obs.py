"""Observability suite for repro.obs: tracing, metrics, profiling.

* metrics registry: typed get-or-create, snapshot/reset, histogram
  percentile math bit-identical to the old serve reservoir
* tracing: zero allocations while disabled (the serve-p50 guard),
  Chrome trace-event JSON round-trip through json.load for a warmed
  serve stream and a CascadeSVM fit, span coverage for plan launches /
  fit iterations / resilience rungs / ingest chunks, @traced, summary tree
* migration contract: plan.cache_stats() / resilience.stats() /
  serve.stats() bitwise-unchanged whether tracing is on or off
* thread safety: exact counts from a threaded hammer over the locked
  registry increments (the bare `+=` these counters replaced lost updates
  under PredictServer worker threads)
* profiler: predicted == measured bytes per node on the 6-op fused chain,
  and the costmodel-drift rule clean on main / provably firing when the
  byte law is broken
"""

import json
import os
import sys
import tempfile
import threading

import numpy as np
import pytest

import repro.resilience as R
import repro.serve as serve
from repro import analysis, obs
from repro.core import expr as expr_mod
from repro.core import plan as plan_mod
from repro.core.dsarray import from_array
from repro.estimators import CascadeSVM, Ridge

pytestmark = pytest.mark.obs

SEED = 20260808


# ---------------------------------------------------------------------------
# workload helpers
# ---------------------------------------------------------------------------


def _six_op_chain(seed=0, shape=(64, 48), bs=(8, 8)):
    rng = np.random.default_rng(seed)
    a = from_array(rng.normal(size=shape).astype(np.float32), bs).lazy()
    return (((a + a) * 2.0 - a).abs() * 0.5 + 0.25)


def _fit_ridge(n=64, m=8):
    rng = np.random.default_rng(SEED)
    x = rng.normal(size=(n, m)).astype(np.float32)
    y = (x @ rng.normal(size=(m, 1))).astype(np.float32)
    return Ridge(alpha=0.1).fit(from_array(x, (16, m)),
                                from_array(y, (16, 1)))


def _serve_stream(est, n_requests=6, m=8):
    reg = serve.ModelRegistry()
    reg.register("m", est, batch_sizes=(4, 16), block_rows=4)
    srv = serve.PredictServer(reg)
    rng = np.random.default_rng(1)
    futs = [srv.submit("m", rng.normal(size=(2, m)).astype(np.float32))
            for _ in range(n_requests)]
    srv.pump()
    return [f.result() for f in futs]


def _names(events):
    return {e["name"] for e in events}


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    c = obs.registry.counter("t.c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert obs.registry.counter("t.c") is c          # get-or-create
    g = obs.registry.gauge("t.g")
    g.set(3)
    g.set_max(7)
    g.set_max(2)                                     # lower: no-op
    assert g.value == 7
    h = obs.registry.histogram("t.h")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["max"] == 4.0 and s["mean"] == 2.5
    with pytest.raises(TypeError):
        obs.registry.gauge("t.c")                    # typed: no shadowing


def test_snapshot_prefix_and_reset_all():
    obs.registry.counter("sn.a").inc(2)
    obs.registry.counter("sn.b").inc(3)
    obs.registry.counter("other.c").inc(1)
    snap = obs.snapshot("sn")
    assert snap == {"sn.a": 2, "sn.b": 3}
    full = obs.snapshot()
    assert full["other.c"] == 1
    obs.reset_all()
    assert obs.snapshot("sn") == {"sn.a": 0, "sn.b": 0}


def test_histogram_percentile_is_nearest_rank():
    # the exact index law the serve latency reservoir has always used:
    # i = min(len-1, round(q * (len-1)))
    h = obs.registry.histogram("t.lat")
    vals = [float(v) for v in range(1, 11)]          # 1..10
    for v in vals:
        h.observe(v)
    s = h.summary()
    srt = sorted(vals)
    for q, key in ((0.50, "p50"), (0.99, "p99")):
        i = min(len(srt) - 1, int(round(q * (len(srt) - 1))))
        assert s[key] == srt[i]


def test_stats_views_are_plain_int_dicts():
    chain = _six_op_chain()
    plan_mod.clear_cache()
    chain.compute()
    cs = plan_mod.cache_stats()
    assert list(cs) == ["hits", "misses", "launches", "opt_runs",
                        "opt_skips", "eager_launches", "aot_compiles"]
    assert all(type(v) is int for v in cs.values())
    assert cs["misses"] == 1 and cs["launches"] == 1
    rs = R.stats()
    assert list(rs) == ["executions", "retries", "degradations",
                        "recoveries", "guard_failures"]
    assert all(type(v) is int for v in rs.values())


# ---------------------------------------------------------------------------
# tracing: the zero-overhead-disabled contract
# ---------------------------------------------------------------------------


def test_disabled_tracing_allocates_no_spans():
    chain = _six_op_chain()
    plan_mod.clear_cache()
    chain.compute()                                  # compile once
    assert not obs.enabled()
    base = obs.span_allocations()
    for _ in range(100):
        chain.compute()                              # hot cached launches
    assert obs.span_allocations() == base == 0
    assert obs.events() == []
    # and the null span really is one shared object, not per-call garbage
    assert obs.span("x") is obs.span("y", a=1)


def test_span_records_chrome_event_and_error_attr():
    obs.enable()
    with obs.span("unit.ok", k=1) as sp:
        sp.set(extra="v")
    with pytest.raises(RuntimeError):
        with obs.span("unit.bad"):
            raise RuntimeError("boom")
    obs.disable()
    evts = obs.events()
    assert [e["name"] for e in evts] == ["unit.ok", "unit.bad"]
    ok, bad = evts
    assert ok["ph"] == "X" and ok["dur"] >= 0 and ok["args"]["extra"] == "v"
    assert bad["args"]["error"] == "RuntimeError"


def test_traced_decorator():
    @obs.traced
    def plain(x):
        return x + 1

    @obs.traced(name="custom.label", tag="t")
    def named(x):
        return x * 2

    assert plain(1) == 2 and named(2) == 4           # disabled: no events
    assert obs.events() == []
    obs.enable()
    plain(1)
    named(2)
    obs.disable()
    names = [e["name"] for e in obs.events()]
    assert "custom.label" in names
    assert any(n.endswith("plain") for n in names)


def test_trace_to_writes_valid_json_and_restores_state():
    assert not obs.enabled()
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.json")
        with obs.trace_to(path):
            assert obs.enabled()
            with obs.span("a.b"):
                pass
        assert not obs.enabled()                     # prior state restored
        with open(path) as f:
            trace = json.load(f)
    assert trace["displayTimeUnit"] == "ms"
    assert [e["name"] for e in trace["traceEvents"]] == ["a.b"]


def test_summary_tree_aggregates_by_name():
    obs.enable()
    for _ in range(3):
        with obs.span("plan.launch"):
            pass
    with obs.span("plan.optimize"):
        pass
    obs.disable()
    text = obs.summary()
    assert "plan" in text and "launch" in text and "optimize" in text
    assert "3" in text                               # the launch count


def _xplane_events(directory, name):
    """``(absolute start ns, duration ns)`` of the host-plane events called
    ``name`` in the profile under ``directory``, and every host event name.
    The reader gives starts from the profile's start, a real-time stamp."""
    import glob
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = ProfileData.from_file(path)
    t0 = next(int(v) for plane in pd.planes for k, v in plane.stats
              if k == "profile_start_time")
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    evs = [ev for line in host.lines for ev in line.events]
    return ([(t0 + int(ev.start_ns), int(ev.duration_ns)) for ev in evs
             if ev.name == name], {ev.name for ev in evs})


def test_span_lands_on_profiler_host_plane_on_the_buffer_clock(tmp_path):
    import time
    import jax
    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("unit.probe", k=1):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    found, names = _xplane_events(str(tmp_path), "unit.probe")
    (evt,) = obs.events()
    assert len(found) == 1, names
    # the bare name: no attribute encoding on the annotation
    assert not any(n.startswith("unit.probe#") for n in names)
    start, dur = found[0]
    assert abs(start - evt["ts"] * 1e3) < 1e6          # within 1 ms
    assert abs(dur - evt["dur"] * 1e3) < 1e6
    assert evt["args"] == {"k": 1}


def test_parent_is_the_enclosing_span_on_the_same_thread():
    obs.enable()
    with obs.span("a.outer"):
        with obs.span("a.inner"):
            t = obs.now_ns()
            obs.record("a.after", t - 1000, t)
        t = threading.Thread(target=lambda: obs.span("b.other").__enter__()
                             .__exit__(None, None, None))
        t.start()
        t.join(10)
        assert not t.is_alive()
    with obs.span("a.next"):
        pass
    obs.disable()
    parent = {e["name"]: e["parent"] for e in obs.events()}
    assert parent == {"a.inner": "a.outer", "a.after": "a.inner",
                      "a.outer": None, "b.other": None, "a.next": None}
    after = next(e for e in obs.events() if e["name"] == "a.after")
    assert after["dur"] == 1.0                       # microseconds


def test_record_buffers_only_while_enabled():
    t = obs.now_ns()
    obs.record("x.off", t, t + 5)
    assert obs.events() == []
    obs.enable()
    obs.record("x.on", t, t + 5000, request=3)
    obs.disable()
    (evt,) = obs.events()
    assert evt["ts"] == t / 1000 and evt["dur"] == 5.0
    assert evt["args"] == {"request": 3} and evt["ph"] == "X"
    assert obs.span_allocations() == 0               # no Span object


def test_disabled_tracing_allocates_nothing_over_fits_and_batches():
    from repro.algorithms import KMeans
    est = _fit_ridge()
    rng = np.random.default_rng(7)
    x = from_array(rng.normal(size=(64, 4)).astype(np.float32), (16, 4))
    KMeans(n_clusters=3, max_iter=2, seed=0).fit(x)
    reg = serve.ModelRegistry()
    reg.register("m", est, batch_sizes=(4,), block_rows=4)
    srv = serve.PredictServer(reg)
    rows = rng.normal(size=(2, 8)).astype(np.float32)
    srv.submit("m", rows)
    srv.pump()
    obs.reset_all()
    assert not obs.enabled()
    _fit_ridge()
    KMeans(n_clusters=3, max_iter=2, seed=0).fit(x)
    for _ in range(100):
        f = srv.submit("m", rows)
        srv.pump()
        f.result()
    assert serve.stats()["batches"] == 100
    assert obs.span_allocations() == 0
    assert obs.events() == []


def test_traced_launch_waits_for_no_device(monkeypatch):
    import jax
    chain = _six_op_chain(seed=4)
    chain.compute()
    waits = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append(x) or x)
    obs.enable()
    chain.compute()
    plan_mod.plan_for(chain).execute_eager()
    obs.disable()
    assert waits == []
    assert [e["name"] for e in obs.events()].count("plan.launch") == 2


def test_fit_driver_spans_nest_under_their_stages():
    from repro.algorithms import KMeans
    plan_mod.clear_cache()
    obs.enable()
    _fit_ridge()
    rng = np.random.default_rng(8)
    x = from_array(rng.normal(size=(64, 4)).astype(np.float32), (16, 4))
    KMeans(n_clusters=3, max_iter=2, seed=0).fit(x)
    obs.disable()
    evts = obs.events()
    names = [e["name"] for e in evts]
    top = [e["name"] for e in evts if e["parent"] is None]
    assert top[:4] == ["fit.validate", "fit.stats", "fit.collect",
                       "fit.solve"]
    assert top[-3:] == ["fit.row_norms", "fit.init", "fit.loop"]
    parent = {(e["name"], e["parent"]) for e in evts}
    # the Gram plan is built under fit.stats and optimized on its miss
    assert ("plan.build", "fit.stats") in parent
    assert ("plan.optimize", "plan.build") in parent
    assert ("plan.launch", "fit.stats") in parent
    assert ("plan.build", "fit.row_norms") in parent
    assert names.count("fit.solve") == 1


def test_device_solve_spans_factor_before_collect_and_refine_in_solve(
        monkeypatch):
    from repro.estimators import linear
    monkeypatch.setattr(linear, "_DEVICE_SOLVE_MIN", 2)
    obs.enable()
    _fit_ridge()
    obs.disable()
    evts = obs.events()
    top = [e["name"] for e in evts if e["parent"] is None]
    assert top[:5] == ["fit.validate", "fit.stats", "fit.factor",
                       "fit.collect", "fit.solve"]
    refines = [e for e in evts if e["name"] == "fit.refine"]
    # at least one correction, then the residual that meets the bound
    assert len(refines) >= 2
    assert {e["parent"] for e in refines} == {"fit.solve"}


def test_serve_queue_records_carry_request_and_batch_ids():
    est = _fit_ridge()
    reg = serve.ModelRegistry()
    reg.register("m", est, batch_sizes=(4, 16), block_rows=4)
    srv = serve.PredictServer(reg)
    rng = np.random.default_rng(9)
    obs.enable()
    # 10 requests of 2 rows: two batches under the 16-row bucket
    futs = [srv.submit("m", rng.normal(size=(2, 8)).astype(np.float32))
            for _ in range(10)]
    srv.pump()
    obs.disable()
    for f in futs:
        f.result()
    evts = obs.events()
    submits = [e["args"]["request"] for e in evts
               if e["name"] == "serve.submit"]
    queues = [e for e in evts if e["name"] == "serve.queue"]
    dispatches = {e["args"]["batch"]: e for e in evts
                  if e["name"] == "serve.dispatch"}
    assert submits == list(range(10))
    assert sorted(q["args"]["request"] for q in queues) == submits
    assert len(dispatches) == 2
    for q in queues:
        d = dispatches[q["args"]["batch"]]
        # the wait ends where its dispatch begins
        assert q["ts"] + q["dur"] <= d["ts"] + 1.0
        assert q["dur"] > 0
    sizes = sorted(d["args"]["requests"] for d in dispatches.values())
    assert sizes == [2, 8]
    assert [sum(q["args"]["batch"] == b for q in queues)
            for b in sorted(dispatches)] == [8, 2]
    assert {"serve.plan", "serve.batch", "serve.slice"} <= _names(evts)


def test_plan_programs_are_named_after_their_roots():
    rng = np.random.default_rng(10)
    x = from_array(rng.normal(size=(32, 8)).astype(np.float32), (16, 8))
    y = from_array(rng.normal(size=(32, 1)).astype(np.float32), (16, 1))
    xl = x.lazy()
    p = plan_mod.plan_for(xl.T @ x, xl.T @ y, xl.sum(axis=0))
    name = "plan_matmul_ta_matmul_ta_sum"
    text = p.lowered().as_text()
    assert f"@jit_{name}" in text
    # nothing but the name differs from the body jitted as ``run``
    run = p._make_run()
    assert run.__name__ == name
    run.__name__ = run.__qualname__ = "run"
    import jax
    plain = jax.jit(run).lower(*p.leaf_values()).as_text()
    assert text.replace(name, "run") == plain
    assert plan_mod.plan_for((x.lazy() * x).sum(axis=1))._make_run() \
        .__name__ == "plan_sum"
    long = plan_mod.program_name([p.roots[0]] * 30)
    assert long == plan_mod.program_name([p.roots[0]] * 30)
    assert long.isidentifier() and len(long) <= 64
    assert long != plan_mod.program_name([p.roots[0]] * 31)


# ---------------------------------------------------------------------------
# span coverage: plan / fit / resilience / serve / ingest
# ---------------------------------------------------------------------------


def test_trace_covers_warmed_serve_stream():
    est = _fit_ridge()
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "serve.json")
        with obs.trace_to(path):
            _serve_stream(est)
        with open(path) as f:
            trace = json.load(f)
    events = trace["traceEvents"]
    names = _names(events)
    assert {"serve.submit", "serve.batch", "serve.dispatch",
            "serve.slice", "plan.launch"} <= names
    assert all(e["ph"] == "X" and "ts" in e and "dur" in e for e in events)
    # every dispatch span names its mode; clean run = attempt 0 throughout
    dispatches = [e for e in events if e["name"] == "serve.dispatch"]
    assert dispatches and all(e["args"]["attempt"] == 0 for e in dispatches)


def test_trace_covers_csvm_fit_iterations():
    rng = np.random.default_rng(3)
    xa = rng.normal(size=(64, 8)).astype(np.float32)
    y = (xa[:, 0] > 0).astype(np.float32)
    x = from_array(xa, (16, 8))
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "fit.json")
        with obs.trace_to(path):
            CascadeSVM(max_iter=2, solver_iters=10, sv_cap=16).fit(x, y)
        with open(path) as f:
            trace = json.load(f)
    events = trace["traceEvents"]
    iters = [e for e in events if e["name"] == "fit.iteration"]
    assert [e["args"]["iteration"] for e in iters] == [1, 2]
    assert all(e["args"]["estimator"] == "CascadeSVM" for e in iters)
    assert "plan.launch" in _names(events)
    # iteration spans ENCLOSE their launches (the tree nests in a viewer)
    launches = [e for e in events if e["name"] == "plan.launch"]
    i0 = iters[0]
    assert any(i0["ts"] <= e["ts"] and
               e["ts"] + e["dur"] <= i0["ts"] + i0["dur"] + 1
               for e in launches)


def test_trace_covers_resilience_retry_rungs():
    rng = np.random.default_rng(4)
    a = from_array(rng.normal(size=(8, 12)).astype(np.float32), (4, 4))
    b = from_array(rng.normal(size=(12, 6)).astype(np.float32), (4, 3))
    with expr_mod.lazy():
        lz = (a @ b) * 2.0 + 1.0
    obs.enable()
    with R.inject(R.FaultSpec(kind="transient", site="plan_execute", at=1)):
        R.run_resilient(lz)
    obs.disable()
    rungs = [e for e in obs.events() if e["name"] == "resilience.rung"]
    assert len(rungs) == 2                           # failed attempt + win
    assert rungs[0]["args"]["attempt"] == 0
    assert rungs[0]["args"]["error"] == "TransientError"
    assert rungs[1]["args"]["attempt"] == 1
    assert "error" not in rungs[1]["args"]
    assert R.stats()["retries"] == 1


def test_trace_covers_ingest_chunks():
    from repro.core.io import load_txt_file
    rng = np.random.default_rng(5)
    ref = rng.normal(size=(32, 6)).astype(np.float32)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "x.csv")
        np.savetxt(path, ref, delimiter=",", fmt="%.6f")
        obs.enable()
        x = load_txt_file(path, (8, 6), chunk_bytes=256)
        obs.disable()
    assert np.allclose(np.asarray(x.collect()), ref, atol=1e-5)
    names = _names(obs.events())
    assert {"ingest.load", "ingest.chunk"} <= names
    chunks = [e for e in obs.events() if e["name"] == "ingest.chunk"]
    assert len(chunks) > 1                           # actually streamed
    assert all(e["args"]["chunk_bytes"] > 0 for e in chunks)


# ---------------------------------------------------------------------------
# migration contract: identical stats traced vs untraced
# ---------------------------------------------------------------------------


def _stats_workload():
    plan_mod.clear_cache()
    est = _fit_ridge()
    _serve_stream(est)
    rng = np.random.default_rng(6)
    a = from_array(rng.normal(size=(8, 12)).astype(np.float32), (4, 4))
    b = from_array(rng.normal(size=(12, 6)).astype(np.float32), (4, 3))
    with expr_mod.lazy():
        lz = (a @ b) * 2.0 + 1.0
    with R.inject(R.FaultSpec(kind="transient", site="plan_execute", at=1)):
        R.run_resilient(lz)
    return (plan_mod.cache_stats(), R.stats(), serve.stats())


def test_stats_identical_with_and_without_tracing():
    untraced = _stats_workload()
    obs.reset_all()
    obs.enable()
    try:
        traced = _stats_workload()
    finally:
        obs.disable()
    for off, on, which in zip(untraced, traced,
                              ("plan", "resilience", "serve")):
        # latency timings differ run to run; counter values must not
        off = dict(off)
        on = dict(on)
        off.pop("latency", None)
        on.pop("latency", None)
        assert off == on, f"{which} stats changed under tracing"


# ---------------------------------------------------------------------------
# thread safety: the locked increments count exactly
# ---------------------------------------------------------------------------


def test_threaded_hammer_counts_exactly():
    import importlib
    from repro.resilience import execute as rex
    # repro.serve re-exports the stats FUNCTION under the same name, so
    # reach the module through importlib
    serve_stats = importlib.import_module("repro.serve.stats")
    n_threads, n_incs = 8, 2500
    c = obs.registry.counter("hammer.c")

    def work():
        for _ in range(n_incs):
            c.inc()
            serve_stats.bump("requests")
            rex._STATS.inc("retries")
            plan_mod._STATS.inc("hits")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)                      # force contention
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(old)
    want = n_threads * n_incs
    assert c.value == want
    assert serve.stats()["requests"] == want
    assert R.stats()["retries"] == want
    assert plan_mod.cache_stats()["hits"] == want


# ---------------------------------------------------------------------------
# profiler + costmodel-drift rule
# ---------------------------------------------------------------------------


def test_profile_six_op_chain_matches_costmodel():
    chain = _six_op_chain()
    plan_mod.clear_cache()
    rep = obs.profile(chain)
    assert rep.nodes                                 # fused body profiled
    for rec in rep.nodes:
        assert rec.measured_bytes == rec.predicted_bytes, rec.site
        assert rec.time_s >= 0.0
    assert rep.drifting() == []
    assert rep.fused_time_s is not None and rep.fused_time_s > 0.0
    text = str(rep)
    assert "within drift tolerance" in text and "fused" in text


def test_profile_accepts_plan_and_skips_fused():
    p = plan_mod.plan_for(_six_op_chain(seed=1))
    rep = obs.profile(p, fused=False, compiled=False)
    assert rep.fused_time_s is None and rep.compiled == {}
    assert rep.eager_total_s == sum(n.time_s for n in rep.nodes)


def test_costmodel_drift_rule_clean_on_real_plans():
    p = plan_mod.plan_for(_six_op_chain(seed=2))
    rep = analysis.check(p, rules=["costmodel-drift"])
    assert rep.ok and rep.findings == []


def test_costmodel_drift_rule_fires_when_law_is_broken(monkeypatch):
    from repro.core import costmodel
    real = costmodel.node_live_bytes
    # a 2x-wrong byte law: every prediction is half reality — well beyond
    # the 1.25x tolerance, so every non-leaf node must be flagged
    monkeypatch.setattr(costmodel, "node_live_bytes",
                        lambda *a, **k: real(*a, **k) / 2.0)
    p = plan_mod.plan_for(_six_op_chain(seed=3))
    rep = analysis.check(p, rules=["costmodel-drift"], fail_on="warn")
    assert not rep.ok
    assert rep.findings and all(f.rule == "costmodel-drift"
                                for f in rep.findings)
    assert "2.00x" in str(rep.findings[0])
