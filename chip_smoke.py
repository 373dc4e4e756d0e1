"""Smoke run of the ds-array main path on TPU chips.

One chip (the default) runs the fit -> serve path at sizes a dislib user
runs:

* phase 0 refuses to go on unless JAX sees TPU devices;
* phase 1 builds a dense array shaped like LIBSVM ``epsilon`` on the device,
  fits ``Ridge`` and ``KMeans``, and computes the Gram on dislib-style
  10,000-row blocks, each against a plain ``jax.numpy`` float32 reference;
* phase 2 serves the fitted Ridge through ``ModelRegistry``/``PredictServer``
  and holds the served rows bitwise equal to direct ``predict``;
* phase 3 writes a sparse svmlight file shaped like LIBSVM ``rcv1.binary``,
  loads it through ``load_svmlight_file``, fits ``CascadeSVM`` and serves
  sparse requests, against a NumPy evaluation of the fitted model.

``--chips 4`` runs only the distributed ops (SUMMA, Cannon, the mirrored
transpose, the psum column sum and the eager ``@``) on a 2x2 mesh, each
against the same op on one device.

Every check prints its error beside its tolerance; any failure exits
non-zero before the last line.  The last line of standard output is one JSON
object naming the device.  Run from the repository root:

    python chip_smoke.py [--seed 0] [--chips {1,4}]
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402
from repro import obs  # noqa: E402
from repro.core import plan  # noqa: E402
from repro.core.dsarray import from_array, random_array  # noqa: E402
from repro.resilience import execute as resilience  # noqa: E402

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Data sizes of every phase.  ``FULL`` is what the script runs; tests
    pass smaller ones to ``run`` to rehearse the same code on the CPU."""

    # LIBSVM epsilon: 400,000 x 2,000 dense
    dense_rows: int = 400_000
    dense_cols: int = 2_000
    dense_block: tuple = (8192, 512)
    # dislib's usual 10,000-row blocks, for the Gram alone
    dislib_rows: int = 200_000
    dislib_block: tuple = (10_000, 512)
    kmeans_k: int = 8
    kmeans_iters: int = 3
    serve_buckets: tuple = (1, 8, 128)
    serve_requests: int = 20
    # LIBSVM rcv1.binary (train): 20,242 x 47,236 at about 0.16% density
    sparse_rows: int = 20_242
    sparse_cols: int = 47_236
    sparse_density: float = 0.0016
    sparse_block_rows: int = 2048
    csvm_iters: int = 3
    sparse_buckets: tuple = (1, 8, 32)
    # four-chip phase: 16,384^2 f32 in (2048, 2048) blocks
    dist_n: int = 16_384
    dist_block: int = 2048


FULL = Sizes()

# share of an rcv1-like document's words drawn from its class's vocabulary
TOPIC_SHARE = 0.25


class Run:
    """Per-phase bookkeeping: wall time, XLA compile seconds, checks."""

    def __init__(self):
        self.compile_s = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self.failed = []

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s["total"] += secs

    def check(self, name: str, err: float, tol: float) -> None:
        ok = bool(err <= tol)
        print(f"  check {name}: error {err!r} tolerance {tol!r} "
              f"{'ok' if ok else 'FAILED'}", flush=True)
        if not ok:
            self.failed.append(name)

    def phase(self, name: str, fn, kernel_mode=None) -> None:
        """Run one phase with zeroed counters, then print its GEMM dispatch
        and resilience counters and hold them to the clean path.  A failed
        check lets the later phases run; ``finish`` then exits non-zero."""
        print(f"phase {name}", flush=True)
        obs.reset_all()
        c0, t0 = self.compile_s["total"], time.perf_counter()
        fn()
        secs = time.perf_counter() - t0
        gemm = obs.snapshot("gemm")
        res = resilience.stats()
        print(f"  gemm {json.dumps(gemm)}", flush=True)
        print(f"  resilience {json.dumps(res)}", flush=True)
        if kernel_mode is not None:
            self.check(f"1 - min(1, gemm.dispatch_{kernel_mode})",
                       max(0, 1 - gemm[f"gemm.dispatch_{kernel_mode}"]), 0)
        self.check("resilience.degradations", res["degradations"], 0)
        self.check("resilience.retries", res["retries"], 0)
        print(f"  time {name}: {secs!r} s, compile "
              f"{self.compile_s['total'] - c0!r} s", flush=True)

    def finish(self) -> None:
        if self.failed:
            raise SystemExit(f"failed checks: {', '.join(self.failed)}")


def _rel_max(got, want) -> float:
    """max |got - want| over max |want|."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _rel_fro(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


# ---------------------------------------------------------------------------
# phase 1: dense fit
# ---------------------------------------------------------------------------


def _ridge_reference(xd, y, alpha):
    """The ridge normal equations with an unpenalized intercept, solved in
    float32 jnp from a HIGHEST-precision Gram."""
    n, m = xd.shape
    g = jnp.matmul(xd.T, xd, precision=HIGHEST)
    xty = jnp.matmul(xd.T, y, precision=HIGHEST)[:, 0]
    s = xd.sum(axis=0)
    a = jnp.zeros((m + 1, m + 1), jnp.float32)
    a = a.at[:m, :m].set(g + alpha * jnp.eye(m, dtype=jnp.float32))
    a = a.at[:m, m].set(s).at[m, :m].set(s).at[m, m].set(n)
    b = jnp.concatenate([xty, jnp.sum(y)[None]])
    theta = jnp.linalg.solve(a, b)
    return g, theta[:m], theta[m]


def _sq_dists(xd, c):
    """Squared distances of every row to every centre, (n, k)."""
    return (jnp.sum(xd * xd, axis=1, keepdims=True)
            - 2.0 * jnp.matmul(xd, c.T, precision=HIGHEST)
            + jnp.sum(c * c, axis=1)[None, :])


def _centre_means(xd, labels, c0):
    """The Lloyd update: the mean of each centre's rows (an empty centre
    stays where it was)."""
    onehot = jax.nn.one_hot(labels, c0.shape[0], dtype=jnp.float32)
    sums = jnp.matmul(onehot.T, xd, precision=HIGHEST)
    counts = onehot.sum(axis=0)[:, None]
    return jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), c0)


def phase_dense(r: Run, s: Sizes, seed: int, state: dict) -> None:
    from repro.algorithms import KMeans
    from repro.estimators import Ridge

    kx, kw, kn, k2 = jax.random.split(jax.random.PRNGKey(seed), 4)
    n, m = s.dense_rows, s.dense_cols
    x = random_array(kx, (n, m), s.dense_block, distribution="normal")
    w_true = from_array(jax.random.normal(kw, (m, 1)), (s.dense_block[1], 1))
    score = (x @ w_true).collect()
    y = jnp.sign(score + 0.5 * jax.random.normal(kn, score.shape))
    y = jnp.where(y == 0, 1.0, y)
    jax.block_until_ready((x.blocks, y))
    print(f"  epsilon-shaped x {x.shape} blocks {x.block_shape} "
          f"({x.blocks.nbytes} bytes on device), y in {{-1, +1}}", flush=True)

    ridge = Ridge(alpha=1.0).fit(x, y)
    gram = (x.lazy().T @ x).compute().collect()
    xd = x.collect()
    g_ref, coef_ref, icpt_ref = _ridge_reference(xd, y, 1.0)
    # f32 sums over all rows in another order; the MXU takes f32 in bf16
    # passes.  A misplaced block errs by a whole off-diagonal entry (~1e-3).
    r.check(f"gram {s.dense_block} blocks, max|G-G_ref|/max|G_ref|",
            _rel_max(gram, g_ref), 1e-4)
    # X^T y takes XLA's default f32 matmul precision (one bf16 pass)
    r.check("ridge coef, |w-w_ref|/|w_ref|",
            _rel_fro(ridge.coef_, coef_ref), 1e-2)
    r.check("ridge intercept, |b-b_ref|", abs(ridge.intercept_ - float(icpt_ref)),
            1e-3)
    state["ridge"] = ridge
    state["dense_rows"] = np.asarray(xd[: 4 * max(s.serve_buckets)])

    km0 = KMeans(n_clusters=s.kmeans_k, max_iter=0, seed=seed).fit(x)
    km1 = KMeans(n_clusters=s.kmeans_k, max_iter=1, seed=seed).fit(x)
    c0 = km0.centers_
    labels_ref = jnp.argmin(_sq_dists(xd, c0), axis=1)
    labels = km0.predict(x).collect()[:, 0]
    # two f32 evaluations of |x|^2 - 2 x.c + |c|^2 split only exact near ties
    r.check("kmeans assignment from the same centres, 1 - agreement",
            float(jnp.mean(labels != labels_ref)), 1e-4)
    c1_ref = _centre_means(xd, labels_ref, c0)
    counts = np.bincount(np.asarray(labels_ref), minlength=s.kmeans_k)
    print(f"  kmeans rows per centre {counts.tolist()}", flush=True)
    # one row moved between centres shifts a centre of c rows by at most
    # max|x - c| / c, under 1e-3 for these sizes
    r.check("kmeans one Lloyd step, max|c - c_ref|",
            float(jnp.abs(km1.centers_ - c1_ref).max()), 1e-2)
    del xd, g_ref
    km = KMeans(n_clusters=s.kmeans_k, max_iter=s.kmeans_iters,
                seed=seed).fit(x)
    r.check("kmeans fit ran its iterations, |n_iter - max_iter|",
            abs(km.n_iter_ - s.kmeans_iters), 0)
    r.check("kmeans centres finite (count of non-finite)",
            int((~jnp.isfinite(km.centers_)).sum()), 0)
    del x

    x2 = random_array(k2, (s.dislib_rows, m), s.dislib_block,
                      distribution="normal")
    gram2 = (x2.lazy().T @ x2).compute().collect()
    x2d = x2.collect()
    r.check(f"gram {s.dislib_block} blocks, max|G-G_ref|/max|G_ref|",
            _rel_max(gram2, jnp.matmul(x2d.T, x2d, precision=HIGHEST)), 1e-4)


# ---------------------------------------------------------------------------
# phase 2: serve the fitted Ridge
# ---------------------------------------------------------------------------


def phase_serve(r: Run, s: Sizes, state: dict) -> None:
    import repro.serve as serve

    ridge, rows = state["ridge"], state["dense_rows"]
    reg = serve.ModelRegistry()
    reg.register("ridge", ridge, batch_sizes=s.serve_buckets)
    srv = serve.PredictServer(reg)
    warm = plan.cache_stats()

    # one request per pump at a bucket's own size, so the served batch has
    # the direct predict's geometry and both run one executable
    sent, served = [], []
    for i in range(s.serve_requests):
        size = s.serve_buckets[i % len(s.serve_buckets)]
        req = rows[i % 4 * size: (i % 4 + 1) * size]
        f = srv.submit("ridge", req)
        srv.pump()
        sent.append(req)
        served.append(f.result())
    # one micro-batch: four requests share the 8-row bucket
    small = s.serve_buckets[1] // 4
    group = [rows[j * small: (j + 1) * small] for j in range(4)]
    futures = [srv.submit("ridge", g) for g in group]
    srv.pump()
    after = plan.cache_stats()
    st = serve.stats()
    print(f"  served {st['responses']} requests in {st['batches']} batches, "
          f"cache hits {st['cache_hits']}", flush=True)
    for key in ("misses", "aot_compiles"):
        r.check(f"plan.cache_stats()['{key}'] growth over the stream",
                after[key] - warm[key], 0)

    mismatched = sum(not np.array_equal(got, np.asarray(
        ridge.predict(req).collect())) for req, got in zip(sent, served))
    batch_direct = np.asarray(ridge.predict(np.concatenate(group)).collect())
    batched = np.concatenate([f.result() for f in futures])
    mismatched += not np.array_equal(batched, batch_direct)
    r.check("served != direct predict (count of requests)", mismatched, 0)

    x = from_array(jnp.asarray(rows[: s.serve_buckets[-1]]),
                   (s.serve_buckets[-1], rows.shape[1]))
    first = np.asarray(ridge.predict(x).collect())
    second = np.asarray(ridge.predict(x).collect())
    r.check("second direct predict on the same array differs",
            int(not np.array_equal(first, second)), 0)
    r.check("caller's array deleted by predict", int(x.blocks.is_deleted()), 0)


# ---------------------------------------------------------------------------
# phase 3: sparse, shaped like rcv1.binary
# ---------------------------------------------------------------------------


def _rcv1_like(s: Sizes, seed: int):
    """A CSR matrix at rcv1's shape: skewed words per document and column
    popularity, positive weights with unit-norm rows, and balanced labels.
    As in rcv1, where the label is a topic, part of each document's words
    come from a vocabulary of its class, so a model of a few support
    vectors can separate the classes."""
    import scipy.sparse as sp

    rng = np.random.default_rng(seed)
    n, m = s.sparse_rows, s.sparse_cols
    want = s.sparse_density * m
    y = rng.permutation(np.arange(n) % 2) * 2 - 1
    # lognormal words per document with mean ``want``; a Zipf(0.7) column
    # popularity puts the commonest term in about 60% of the documents
    per_row = np.maximum(1, rng.lognormal(np.log(want) - 0.18, 0.6, n)
                         .astype(np.int64))
    pop = 1.0 / np.arange(1, m + 1) ** 0.7
    cdf = np.cumsum(pop / pop.sum())
    order = rng.permutation(m)
    cols = order[np.minimum(np.searchsorted(cdf, rng.random(per_row.sum())),
                            m - 1)]
    # a quarter of the words from the class's own 100-word vocabulary,
    # taken from the rare end of the popularity order
    doc = np.repeat(np.arange(n, dtype=np.int64), per_row)
    topical = rng.random(doc.size) < TOPIC_SHARE
    vocab = order[-200:].reshape(2, 100)
    cols = np.where(topical, vocab[(y[doc] + 1) // 2,
                                   rng.integers(0, 100, doc.size)], cols)
    keys = np.unique(doc * m + cols)
    rows, cols = keys // m, keys % m
    vals = (rng.random(keys.size) + 0.1).astype(np.float32)
    mat = sp.csr_matrix((vals, (rows, cols)), shape=(n, m), dtype=np.float32)
    norms = np.sqrt(np.asarray(mat.multiply(mat).sum(axis=1))).ravel()
    mat = sp.diags((1.0 / norms).astype(np.float32)) @ mat
    return mat.astype(np.float32).tocsr(), y


def _write_svmlight(path: str, mat, y) -> None:
    with open(path, "w") as f:
        for i in range(mat.shape[0]):
            lo, hi = mat.indptr[i], mat.indptr[i + 1]
            pairs = " ".join(f"{c + 1}:{v!r}" for c, v in
                             zip(mat.indices[lo:hi].tolist(),
                                 mat.data[lo:hi].tolist()))
            f.write(f"{int(y[i])} {pairs}\n")


def phase_sparse(r: Run, s: Sizes, seed: int) -> None:
    import repro.serve as serve
    from repro.core import sparse as sparse_mod
    from repro.core.io import load_svmlight_file
    from repro.estimators import CascadeSVM

    t0 = time.perf_counter()
    mat, y = _rcv1_like(s, seed)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "rcv1_like.svm")
        _write_svmlight(path, mat, y)
        print(f"  rcv1-shaped {mat.shape}, {mat.nnz} nonzeros "
              f"(density {mat.nnz / (mat.shape[0] * mat.shape[1])!r}), "
              f"{os.path.getsize(path)} bytes of svmlight written in "
              f"{time.perf_counter() - t0!r} s", flush=True)
        t0 = time.perf_counter()
        x, y_ds = load_svmlight_file(path, (s.sparse_block_rows, mat.shape[1]),
                                     n_features=mat.shape[1],
                                     store_sparse=True)
        jax.block_until_ready((x.blocks.data, y_ds.blocks))
    print(f"  loaded as {x.block_format} blocks {x.block_shape} in "
          f"{time.perf_counter() - t0!r} s", flush=True)
    # one block row through the compiled BCOO todense / fromdense pair
    head = x[: s.sparse_block_rows].todense()
    want = mat[: s.sparse_block_rows].toarray()
    r.check("todense of one block row, count of entries unequal to scipy",
            int(np.sum(np.asarray(head.collect()) != want)), 0)
    back = head.tosparse().todense()
    r.check("tosparse -> todense round trip, count of unequal entries",
            int(np.sum(np.asarray(back.collect()) != want)), 0)
    del head, back, want

    print(f"  CascadeSVM max_iter cut from 5 to {s.csvm_iters}", flush=True)
    csvm = CascadeSVM(max_iter=s.csvm_iters).fit(x, y_ds)
    dec = np.asarray(csvm.decision_function(x).collect(), np.float64).ravel()
    sv = csvm.sv_.astype(np.float64)
    x_sq = np.asarray(mat.multiply(mat).sum(axis=1), np.float64).ravel()
    d2 = x_sq[:, None] - 2.0 * (mat.astype(np.float64) @ sv.T) \
        + (sv * sv).sum(axis=1)[None, :]
    dec_ref = (np.exp(-csvm.gamma_ * np.maximum(d2, 0.0))
               @ (csvm.dual_coef_ * csvm.sv_y_) + csvm.intercept_)
    acc = float(np.mean(np.where(dec > 0, 1, -1) == y))
    print(f"  {csvm.n_iter_} cascade iterations, {csvm.n_sv_} support "
          f"vectors, training accuracy {acc!r}", flush=True)
    # f32 kernel expansion |x|^2 - 2 x.sv + |sv|^2 against a float64 one
    r.check("csvm decision values, max|d-d_ref|/max|d_ref|",
            _rel_max(dec, dec_ref), 1e-3)

    cap = max(s.sparse_buckets)
    nse = cap * int(np.diff(mat.indptr).max())
    reg = serve.ModelRegistry()
    reg.register("csvm", csvm, batch_sizes=s.sparse_buckets,
                 formats=("bcoo",), nse=nse)
    srv = serve.PredictServer(reg)
    reqs, futures, lo = [], [], 0
    for size in s.sparse_buckets:
        reqs.append(mat[lo: lo + size])
        futures.append(srv.submit("csvm", reqs[-1]))
        srv.pump()
        lo += size
    mismatched = 0
    for req, f in zip(reqs, futures):
        direct = csvm.predict(sparse_mod.from_scipy(req, req.shape))
        mismatched += not np.array_equal(f.result(),
                                         np.asarray(direct.collect()))
    r.check("served sparse labels != direct predict (count)", mismatched, 0)


# ---------------------------------------------------------------------------
# four chips: distributed ops against one device
# ---------------------------------------------------------------------------


def phase_distributed(r: Run, s: Sizes, seed: int) -> None:
    from jax.sharding import Mesh
    from repro.core.shmap_ops import (cannon_matmul, colsum_psum,
                                      summa_matmul, transpose_pp)

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs).reshape(2, 2), ("data", "model"))
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    shape, block = (s.dist_n, s.dist_n), (s.dist_block, s.dist_block)
    a = random_array(ka, shape, block, distribution="normal")
    b = random_array(kb, shape, block, distribution="normal")
    jax.block_until_ready((a.blocks, b.blocks))

    def in_use():
        return [d.memory_stats()["bytes_in_use"]
                if d.memory_stats() else 0 for d in devs]

    before = in_use()
    ad, bd = a.distribute(mesh), b.distribute(mesh)
    jax.block_until_ready((ad.blocks, bd.blocks))
    grown = [x1 - x0 for x0, x1 in zip(before, in_use())]
    quarter = (a.blocks.nbytes + b.blocks.nbytes) / 4
    print(f"  bytes_in_use growth per device after distribute: {grown} "
          f"(a quarter of both operands: {quarter!r})", flush=True)
    if before != [0] * 4:      # backends that report memory
        r.check("max |growth - quarter| / quarter",
                max(abs(g - quarter) for g in grown) / quarter, 0.05)

    one = a @ b                                    # the one-device reference
    want = np.asarray(one.collect())
    for name, got in (("summa_matmul", summa_matmul(ad, bd, mesh)),
                      ("cannon_matmul", cannon_matmul(ad, bd, mesh)),
                      ("eager @ on distributed operands", ad @ bd)):
        # the one-device product and the sharded einsum may take different
        # f32 matmul precisions (bf16 passes) on the MXU
        r.check(f"{name}, |C-C_one|_F/|C_one|_F",
                _rel_fro(got.collect(), want), 1e-2)
    del want
    r.check("transpose_pp, count of entries unequal to one-device a.T",
            int(np.sum(np.asarray(transpose_pp(ad, mesh).collect())
                       != np.asarray(a.transpose().collect()))), 0)
    r.check("colsum_psum, max|s-s_one|/max|s_one|",
            _rel_max(colsum_psum(ad, mesh).collect(), a.sum(axis=0).collect()),
            1e-4)


# ---------------------------------------------------------------------------


def run(seed: int = 0, chips: int = 1, sizes: Sizes = FULL,
        require_tpu: bool = True) -> dict:
    """Run the phases and return the device record of the last line.
    ``require_tpu=False`` lets tests rehearse on the CPU (with the Pallas
    kernel forced into the interpreter); the record then names the CPU."""
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"phase 0: device platform {dev['platform']} kind {dev['kind']} "
          f"count {dev['count']}", flush=True)
    if require_tpu and dev["platform"] != "tpu":
        raise SystemExit(f"no TPU found: JAX sees {dev['platform']} devices")
    if dev["count"] < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, "
                         f"JAX sees {dev['count']}")
    r = Run()
    if chips == 4:
        r.phase("distributed (2x2 mesh)",
                lambda: phase_distributed(r, sizes, seed))
        r.finish()
        return dev
    kernel = "pallas" if require_tpu else "interpret"
    state: dict = {}
    r.phase("1 dense fit", lambda: phase_dense(r, sizes, seed, state),
            kernel_mode=kernel)
    r.phase("2 serve", lambda: phase_serve(r, sizes, state))
    r.phase("3 sparse", lambda: phase_sparse(r, sizes, seed))
    r.finish()
    return dev


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    print(f"compile cache: {repro.enable_compile_cache(ROOT / '.jax_cache')}",
          flush=True)
    dev = run(seed=args.seed, chips=args.chips)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
