"""Closed loop: one caller, and the next call starts when the last one has
returned.  The workload file names the end-to-end metric (``metric``), the
window's seconds over the completed calls, and the host annotation put
around each call (``annotation``).  The last call may run past the given
seconds; the window then ends with it, so every call is whole."""

from __future__ import annotations

import time

import jax

from bench.lib.runner import WindowResult


def run(cell, entry, state, seconds, win) -> WindowResult:
    label = cell.params["annotation"]
    answers = []
    with win:
        end = win.t0 + seconds
        while True:
            with jax.profiler.TraceAnnotation(label):
                answers.append(entry.call(state))
            if time.perf_counter() >= end:
                break
    n = len(answers)
    return WindowResult(attempted=n, failed=0,
                        metrics={cell.params["metric"]: win.elapsed / n},
                        answers=answers, calls=n)
