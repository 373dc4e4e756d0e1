"""The trace reduction and the peaks table: interval arithmetic on
hand-made events, the reduction of a trace recorded here on the CPU, and
the operations and bytes against hand counts."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench.lib import peaks, trace as tr


def test_union_clip_and_gaps():
    iv = [(5, 9), (0, 2), (1, 3), (8, 12), (20, 20)]
    assert tr.union(iv) == [(0, 3), (5, 12)]
    assert tr.total(tr.union(iv)) == 10
    assert tr.gaps([(2, 3), (5, 10)], 0, 15) == [(0, 2), (3, 5), (10, 15)]


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Ev(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_reduce_hand_made_tpu_trace():
    host = _Plane("/host:CPU", [_Line("python", [
        ("window", 100, 1000), ("fit", 100, 400), ("submit", 700, 100),
        ("before", 0, 50)])])
    dev0 = _Plane("/device:TPU:0", [
        _Line("XLA Ops", [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 150,
                           100), ("all-gather.2", 200, 100),
                          ("stacked_matmul", 600, 50), ("late", 1090, 50)]),
        _Line("XLA Modules", [("jit_run", 150, 500)])])
    dev1 = _Plane("/device:TPU:1", [
        _Line("XLA Ops", [("fusion.1", 150, 20)])])
    red = tr.reduce_profile(_Profile([host, dev1, dev0]))
    assert red.window == (100, 1100)
    full = red.fullest()
    assert full.name == "/device:TPU:0"
    # 150-300 (two overlapping ops), 600-650, 1090-1100 (clipped)
    assert full.busy == [(150, 300), (600, 650), (1090, 1100)]
    assert red.idle_share() == pytest.approx(1 - 210 / 1000)
    assert full.op_ns(lambda n: "all-gather" in n) == 100
    assert full.module_ns(lambda n: "run" in n) == 500
    gaps = red.idle_gaps()
    assert gaps[0] == ["submit", pytest.approx(440e-9)]      # 650-1090
    assert ["fit", pytest.approx(300e-9)] in gaps             # 300-600
    # summed over the devices, averaged over their count
    assert red.top_ops(1) == [["fusion.1", pytest.approx(60e-9)]]
    assert red.busy_s() == pytest.approx((210 + 20) / 2 / 1e9)


def test_reduce_recorded_cpu_trace(tmp_path):
    a = jnp.ones((384, 384), jnp.float32)
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    f(a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("fit"):
                f(a).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    red = tr.load(str(tmp_path))
    dev = red.fullest()
    assert red.window_ns >= 60e6
    assert 0 < dev.busy_ns < red.window_ns
    # the three sleeps leave the device idle for at least 60 ms
    assert red.idle_share() * red.window_ns >= 55e6
    assert all(red.window[0] <= s < e <= red.window[1] for s, e in dev.busy)
    names = [n for n, _ in red.top_ops()]
    assert any("dot" in n for n in names), names
    assert {g[0] for g in red.idle_gaps()} <= {"fit", "host idle"}


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
    with pytest.raises(KeyError):
        peaks.roofline_s(1.0, 1.0, "cpu")


def test_flops_and_bytes_against_hand_counts():
    # x'x of a 3 x 2 operand: 2 x 2 outputs, each 3 multiplies and 3 adds
    assert peaks.gram_flops(3, 2) == 2 * 2 * 3 * 2
    # x read as A and as B (2 * 6 floats) and the 2 x 2 result
    assert peaks.gram_bytes(3, 2) == 4 * (12 + 4)
    # 10 slots: 10 float32 values and 10 (row, col) int32 pairs
    assert peaks.bcoo_bytes(10) == 10 * 4 + 10 * 2 * 4
    # 197 TFLOP at the peak is one second; 819 GB at the bandwidth too
    assert peaks.roofline_s(197e12, 1.0, "TPU v5 lite") == 1.0
    assert peaks.roofline_s(1.0, 819e9, "TPU v5 lite") == 1.0
