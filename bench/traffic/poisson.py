"""Open loop: requests arrive as a Poisson process at a fixed rate, whether
or not earlier ones are done.

The workload file gives ``rate_per_s``, the request sizes in rows
(``sizes``) with their shares (``weights``), and ``latency_metrics``: each
end-to-end metric's name with the percentile it reports.  Arrival times,
the order of the sizes and the rows of each request (a slice of the
entry's ``pool``) are drawn from the seed before the window opens.  One thread submits each
request when it is due; another waits for the answers in order and stamps
each when it arrives.  A request's latency runs from when it was due, so a
late generator or a queue counts against it.  Every request due in the
window is waited for, up to a minute past its close; one that errs or
never comes is failed.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from bench.lib.runner import WindowResult

GRACE_S = 60.0
# the parameters a CPU test holds
TINY_PARAMS = dict(pool_rows=600, rate_per_s=200)


def schedule(cell, seconds: float, pool_rows: int):
    """``(due, sizes, offsets)`` of every request of the window.  Every
    seed gets the same work: ``rate_per_s * seconds`` requests with the
    sizes in the shares of ``weights``; the seed draws their order, their
    rows and their arrival times, as a Poisson process given its count
    (``count`` sorted uniform times in the window)."""
    p = cell.params
    rng = cell.rng("arrivals")
    count = int(round(float(p["rate_per_s"]) * seconds))
    w = np.asarray(p["weights"], np.float64)
    share = w / w.sum() * count
    each = np.floor(share).astype(np.int64)
    extra = np.argsort(each - share, kind="stable")[: count - each.sum()]
    each[extra] += 1
    sizes = rng.permutation(np.repeat(np.asarray(p["sizes"]), each))
    gaps = rng.exponential(1.0, count + 1)
    due = np.cumsum(gaps[:-1]) * (seconds / gaps.sum())
    offsets = rng.integers(0, pool_rows - sizes + 1)
    return due, sizes, offsets


def run(cell, entry, state, seconds, win) -> WindowResult:
    pool = state["pool"]
    due, sizes, offsets = schedule(cell, seconds, pool.shape[0])
    count = due.size
    payloads = [pool[o:o + s] for o, s in zip(offsets, sizes)]
    futures = [None] * count
    submitted = threading.Semaphore(0)
    done = np.full(count, np.nan)
    results = [None] * count
    errors = [0]

    def collect(deadline: float) -> None:
        for i in range(count):
            submitted.acquire()
            try:
                results[i] = futures[i].result(
                    timeout=max(0.0, deadline - time.perf_counter()))
                done[i] = time.perf_counter()
            except TimeoutError:
                pass
            except Exception:                       # noqa: BLE001
                errors[0] += 1

    late = np.zeros(count)
    entry.start(state)
    try:
        with win:
            t0 = win.t0
            waiter = threading.Thread(target=collect,
                                      args=(t0 + seconds + GRACE_S,),
                                      name="bench-collect")
            waiter.start()
            for i in range(count):
                wait = t0 + due[i] - time.perf_counter()
                if wait > 0:
                    with jax.profiler.TraceAnnotation("wait"):
                        time.sleep(wait)
                late[i] = time.perf_counter() - t0 - due[i]
                with jax.profiler.TraceAnnotation("submit"):
                    futures[i] = entry.submit(state, payloads[i])
                submitted.release()
            rest = t0 + seconds - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
            open_at_close = int(np.sum(np.isnan(done)))
        waiter.join(GRACE_S + 5)
    finally:
        entry.stop(state)
    ok = ~np.isnan(done)
    lat_ms = (done[ok] - (t0 + due[ok])) * 1e3
    metrics = {name: float(np.percentile(lat_ms, q))
               for name, q in cell.params["latency_metrics"].items()}
    answers = [(int(offsets[i]), int(sizes[i]), results[i])
               for i in np.flatnonzero(ok)]
    notes = {
        "requests due": count,
        "offered rate per s": count / seconds,
        "generator late ms p50/p99/max":
            [float(np.percentile(late, q)) * 1e3 for q in (50, 99)]
            + [float(late.max()) * 1e3],
        "requests open at the close": open_at_close,
        "rows per s served": float(sizes[ok].sum() / win.elapsed),
    }
    return WindowResult(attempted=count, failed=int(count - ok.sum()),
                        metrics=metrics, answers=answers, calls=int(ok.sum()),
                        notes=notes)
