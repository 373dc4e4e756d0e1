"""K-means on ds-arrays (paper §5.5) + the Dataset-baseline variant.

The paper uses K-means as the control experiment: its parallelization
(per-partition partial sums + a reduction) is representation-neutral, so
ds-arrays must match Datasets.  Here the per-block-row "tasks" are one fused
SPMD op over the stacked block tensor; the reduction tree becomes a psum over
the `data` mesh axis when sharded.

The hot inner loop (pairwise distances + argmin + one-hot partial sums) is
also available as a fused Pallas kernel (``repro.kernels.kmeans``) — that is
the TPU adaptation of the paper's per-Subset task.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse
from jax.experimental.sparse import BCOO

from repro.core.dsarray import DsArray, from_array
from repro.core.dataset_baseline import Dataset
from repro.estimators.base import BaseEstimator, _FitCheckpoint, \
    _fire, _iter_span
from repro.obs import tracing as _tracing


# The distance and centre-sum contractions run at full f32 precision: XLA's
# default on a TPU takes f32 operands in one bf16 pass, which sends about one
# row in a thousand of a high-dimensional dataset to a centre that is not its
# nearest.  Each contraction here reads the whole array against only k
# centres, a few flops per byte, so the extra MXU passes should stay hidden
# behind the HBM reads.
_F32 = jax.lax.Precision.HIGHEST


def _row_sq_norms(x: DsArray) -> jnp.ndarray:
    """Per-row squared norms ``(gn, bn)`` via ONE fused lazy plan.

    ``(x*x).sum(axis=1)`` recorded lazily lowers to a single jitted
    square+row-reduce pass over the stacked tensor (mul fused into the
    reduction, zero remasks on the ZERO pad).  The assignment step is
    ``‖x‖² − 2·x·cᵀ + ‖c‖²``: ``‖x‖²`` does not change across Lloyd
    iterations, so it is computed once here and threaded through
    ``_center_stats`` instead of being re-derived per iteration (and the
    structurally-hashed plan is shared by fit/predict/score)."""
    s = (x.lazy() * x).sum(axis=1).compute()        # (n, 1) ds-array
    gn, bn = x.blocks.shape[0], x.blocks.shape[2]
    return s.blocks.reshape(gn, bn).astype(jnp.float32)


def _dots(blocks, c_blocks: jnp.ndarray) -> jnp.ndarray:
    """``x · cᵀ`` summed over feature blocks: (gn, bn, k).

    A BCOO-blocked x contracts its stored entries directly against the
    center blocks (one ``bcoo_dot_general`` over the (gm, bm) feature dims
    — nnz-proportional work, the CSVM/k-means payoff of sparse blocks); the
    dense stacked tensor keeps the einsum.
    """
    if isinstance(blocks, BCOO):
        return jsparse.bcoo_dot_general(
            blocks, c_blocks, dimension_numbers=(((1, 3), (1, 2)), ((), ())))
    return jnp.einsum("ijab,kjb->iak", blocks, c_blocks, precision=_F32,
                      preferred_element_type=jnp.float32)


def _center_stats(blocks, row_valid: jnp.ndarray,
                  centers: jnp.ndarray, x_sq: jnp.ndarray,
                  n_cols: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Distance + assign + partial sums over the stacked block tensor.

    blocks:    (gn, gm, bn, bm) feature-blocked samples (pad = 0), dense
               stacked tensor OR stacked BCOO (sparse rows never densify:
               both contractions run through bcoo_dot_general)
    row_valid: (gn, bn) bool
    centers:   (k, m_padded)    pad columns zero
    x_sq:      (gn, bn) per-row squared norms (see ``_row_sq_norms``)
    returns (labels (gn, bn), sums (k, m_padded), counts (k,))
    """
    gn, gm, bn, bm = blocks.shape
    k = centers.shape[0]
    c_blocks = centers.reshape(k, gm, bm)
    dots = _dots(blocks, c_blocks)                          # (gn, bn, k)
    c_sq = jnp.einsum("km,km->k", centers, centers, precision=_F32,
                      preferred_element_type=jnp.float32)
    dist = x_sq[..., None] - 2.0 * dots + c_sq[None, None, :]
    labels = jnp.argmin(dist, axis=-1)                      # (gn, bn)
    onehot = jax.nn.one_hot(labels, k, dtype=blocks.dtype)  # (gn, bn, k)
    onehot = onehot * row_valid[..., None].astype(blocks.dtype)
    if isinstance(blocks, BCOO):
        # onehotᵀ · x: contract the (gn, bn) sample dims -> (k, gm, bm)
        sums = jsparse.bcoo_dot_general(
            onehot, blocks, dimension_numbers=(((0, 1), (0, 2)), ((), ())))
    else:
        sums = jnp.einsum("iak,ijab->kjb", onehot, blocks, precision=_F32,
                          preferred_element_type=jnp.float32)
    sums = sums.reshape(k, gm * bm)
    counts = onehot.sum(axis=(0, 1))
    return labels, sums, counts


@functools.partial(jax.jit, static_argnames=("n_cols", "tol", "max_iter"))
def _kmeans_run(blocks, centers0, row_valid, x_sq, n_cols, tol, max_iter):
    """Lloyd iterations as a jitted while_loop (module-level so repeated
    ``fit`` calls hit the jit cache)."""

    def cond(state):
        _, shift, it = state
        return (shift > tol) & (it < max_iter)

    def body(state):
        centers, _, it = state
        _, sums, counts = _center_stats(blocks, row_valid, centers,
                                        x_sq, n_cols)
        safe = jnp.maximum(counts, 1.0)[:, None]
        new = jnp.where(counts[:, None] > 0, sums / safe, centers)
        shift = jnp.sqrt(((new - centers) ** 2).sum())
        return new, shift, it + 1

    return jax.lax.while_loop(cond, body, (centers0, jnp.float32(jnp.inf), 0))


@functools.partial(jax.jit, static_argnames=("n_cols",))
def _kmeans_step(blocks, centers, row_valid, x_sq, n_cols):
    """ONE Lloyd iteration (same math as ``_kmeans_run``'s body) — the
    host-driven loop used when per-iteration checkpointing is requested,
    where the device-resident while_loop cannot yield control."""
    _, sums, counts = _center_stats(blocks, row_valid, centers, x_sq, n_cols)
    safe = jnp.maximum(counts, 1.0)[:, None]
    new = jnp.where(counts[:, None] > 0, sums / safe, centers)
    shift = jnp.sqrt(((new - centers) ** 2).sum())
    return new, shift


def _kmeanspp_init(x: np.ndarray, k: int, rng: np.random.Generator) -> np.ndarray:
    """k-means++ seeding (Arthur & Vassilvitskii) — D² sampling."""
    n = x.shape[0]
    centers = [x[rng.integers(n)]]
    d2 = ((x - centers[0]) ** 2).sum(-1)
    for _ in range(1, k):
        tot = d2.sum()
        # degenerate data (every remaining point coincides with a center):
        # D² sampling is undefined, fall back to uniform
        p = d2 / tot if tot > 0 else np.full(n, 1.0 / n)
        centers.append(x[rng.choice(n, p=p)])
        d2 = np.minimum(d2, ((x - centers[-1]) ** 2).sum(-1))
    return np.stack(centers).astype(x.dtype)


@functools.partial(jax.jit)
def _d2_to_center(blocks, row_valid: jnp.ndarray,
                  center: jnp.ndarray, x_sq: jnp.ndarray) -> jnp.ndarray:
    """Per-row squared distance to one center, over the stacked tensor.

    ``center`` is the (gm*bm,)-padded row; both the block pad and the center
    pad are zero, so the squared difference vanishes on pad columns.  Dense
    blocks use the numerically-nicer squared-difference einsum; BCOO blocks
    use the ``‖x‖² − 2·x·c + ‖c‖²`` expansion so only stored entries are
    touched.  Returns (gn, bn) with invalid rows zeroed.
    """
    gn, gm, bn, bm = blocks.shape
    c_blocks = center.reshape(gm, bm)
    if isinstance(blocks, BCOO):
        dots = jsparse.bcoo_dot_general(
            blocks, c_blocks, dimension_numbers=(((1, 3), (0, 1)), ((), ())))
        c_sq = jnp.sum(center * center)
        d2 = jnp.maximum(x_sq - 2.0 * dots + c_sq, 0.0)
    else:
        diff = blocks - c_blocks[None, :, None, :]
        d2 = jnp.einsum("ijab,ijab->ia", diff, diff, precision=_F32,
                        preferred_element_type=jnp.float32)
    return d2 * row_valid.astype(d2.dtype)


def _kmeanspp_init_ds(x: DsArray, k: int, rng: np.random.Generator,
                      row_valid: jnp.ndarray, x_sq: jnp.ndarray) -> jnp.ndarray:
    """Block-native k-means++: never materializes the global array.

    The seed version did ``x.collect()`` — O(n·m) single-host memory, the
    exact materialization tax the ds-array is meant to avoid.  Here each D²
    pass is one fused op over the stacked tensor (nnz-proportional for BCOO
    blocks); only the O(n) distance vector and the O(m) chosen rows ever
    reach the host.
    """
    n, m = x.shape
    gn, gm, bn, bm = x.blocks.shape

    def fetch_row(i: int) -> jnp.ndarray:
        if x.is_sparse:
            # one block row's stored entries scatter into the padded row
            from repro.core import sparse as sparse_mod
            return sparse_mod.fetch_row_dense(x, int(i))
        # block-native single-row gather -> (1, m) -> padded (gm*bm,)
        row = x[int(i)].collect().ravel()
        return jnp.pad(row, (0, gm * bm - m))

    centers = [fetch_row(int(rng.integers(n)))]
    d2 = _d2_to_center(x.blocks, row_valid, centers[0], x_sq)
    for _ in range(1, k):
        d = np.maximum(np.asarray(d2, dtype=np.float64).reshape(-1)[:n], 0.0)
        tot = d.sum()
        # degenerate data (all rows coincide with a center): uniform fallback
        p = d / tot if tot > 0 else np.full(n, 1.0 / n)
        centers.append(fetch_row(int(rng.choice(n, p=p))))
        d2 = jnp.minimum(d2, _d2_to_center(x.blocks, row_valid, centers[-1],
                                           x_sq))
    return jnp.stack(centers)[:, : gm * bm]


@dataclasses.dataclass
class KMeans(BaseEstimator):
    """dislib-style estimator: ``KMeans(...).fit(x)`` with x a ds-array.

    Implements the ``repro.estimators`` contract (``get_params`` /
    ``set_params`` from the dataclass fields, trailing-underscore fitted
    state); ``score`` is the clustering convention (negative inertia)
    rather than the classifier/regressor mixins'."""

    n_clusters: int = 8
    max_iter: int = 20
    tol: float = 1e-4
    seed: int = 0

    centers_: Optional[jnp.ndarray] = None
    n_iter_: int = 0

    def _row_valid(self, x: DsArray) -> jnp.ndarray:
        gn, gm, bn, bm = x.blocks.shape
        gi = jax.lax.broadcasted_iota(jnp.int32, (gn, bn), 0)
        bi = jax.lax.broadcasted_iota(jnp.int32, (gn, bn), 1)
        return (gi * bn + bi) < x.shape[0]

    def fit(self, x: DsArray, y=None, checkpoint_dir: Optional[str] = None,
            resume: Optional[str] = None) -> "KMeans":
        del y                     # unsupervised; kept for the fit(x, y) shape
        with self._driver_scope():
            return self._fit(x, checkpoint_dir=checkpoint_dir, resume=resume)

    def _fit(self, x: DsArray, checkpoint_dir: Optional[str] = None,
             resume: Optional[str] = None) -> "KMeans":
        x = self._validate_x(x).ensure_zero_pad()  # contractions read raw blocks
        n, m = x.shape
        row_valid = self._row_valid(x)
        # assignment-step invariant ‖x‖², hoisted out of the Lloyd loop and
        # computed by one fused lazy plan (was re-derived every iteration);
        # for BCOO blocks the lazy plan is the sparse x*x -> row-sum pair,
        # and the init + Lloyd contractions below never densify x
        with _tracing.span("fit.row_norms"):
            x_sq = _row_sq_norms(x)
        # block-native k-means++ init (k D² passes, each one fused op over the
        # stacked tensor; no x.collect() — the array never leaves the devices)
        with _tracing.span("fit.init"):
            init = _kmeanspp_init_ds(x, self.n_clusters,
                                     np.random.default_rng(self.seed),
                                     row_valid, x_sq)
        if checkpoint_dir is None and resume is None:
            # clean path: the device-resident jitted while_loop, untouched —
            # the iterations live inside ONE launch, so the trace gets one
            # fit.loop span instead of per-iteration fit.iteration spans.
            # The span times the loop's dispatch; the loop's device time is
            # the _kmeans_run program on the device trace
            with _tracing.span("fit.loop", estimator=type(self).__name__,
                               max_iter=self.max_iter):
                centers, _, iters = _kmeans_run(x.blocks, init, row_valid,
                                                x_sq, m, self.tol,
                                                self.max_iter)
            self.centers_ = centers[:, :m]
            self.n_iter_ = int(iters)
            return self
        # checkpointing path: Lloyd driven from the host (one jitted step
        # per iteration, same math) so every iteration can commit
        centers = init
        it = 0
        start_it = 1
        done = False
        if resume is not None:
            got = _FitCheckpoint(resume, type(self).__name__).load()
            if got is not None:
                it0, st = got
                centers = jnp.asarray(st["centers"])
                it = it0
                done = bool(st["done"])
                start_it = it0 + 1
        ckpt = _FitCheckpoint(checkpoint_dir, type(self).__name__) \
            if checkpoint_dir is not None else None
        if not done:
            for it in range(start_it, self.max_iter + 1):
                _fire("fit_iteration", estimator=type(self).__name__,
                      iteration=it)
                with _iter_span(self, it):
                    centers, shift = _kmeans_step(x.blocks, centers, row_valid,
                                                  x_sq, m)
                    done = bool(shift <= self.tol)
                    if ckpt is not None:
                        ckpt.save(it, {"centers": centers, "done": done})
                    if done:
                        break
        self.centers_ = centers[:, :m]
        self.n_iter_ = it
        return self

    def predict(self, x: DsArray) -> DsArray:
        """Labels as a new (n, 1) ds-array — the paper's API fix (predict
        returns a NEW distributed array instead of mutating the input)."""
        self._check_fitted("centers_")
        with self._driver_scope():
            x = self._validate_x(x).ensure_zero_pad()
            gn, gm, bn, bm = x.blocks.shape
            m_pad = gm * bm
            centers = jnp.pad(self.centers_,
                              ((0, 0), (0, m_pad - self.centers_.shape[1])))
            labels, _, _ = _center_stats(x.blocks, self._row_valid(x),
                                         centers, _row_sq_norms(x),
                                         x.shape[1])
            flat = labels.reshape(-1, 1).astype(jnp.int32)[: x.shape[0]]
            return from_array(flat, (x.block_shape[0], 1))

    def score(self, x: DsArray, y=None) -> float:
        """Negative inertia (sum of squared distances to nearest center)."""
        del y
        self._check_fitted("centers_")
        x = self._validate_x(x).ensure_zero_pad()
        gn, gm, bn, bm = x.blocks.shape
        m_pad = gm * bm
        centers = jnp.pad(self.centers_, ((0, 0), (0, m_pad - self.centers_.shape[1])))
        c_blocks = centers.reshape(-1, gm, bm)
        dots = _dots(x.blocks, c_blocks)
        x_sq = _row_sq_norms(x)
        c_sq = jnp.einsum("km,km->k", centers, centers, precision=_F32)
        dist = x_sq[..., None] - 2 * dots + c_sq[None, None, :]
        best = dist.min(axis=-1)
        best = best * self._row_valid(x)
        return float(-best.sum())


# ---------------------------------------------------------------------------
# Dataset-baseline K-means (paper Fig. 9 parity experiment)
# ---------------------------------------------------------------------------


def kmeans_dataset(ds: Dataset, n_clusters: int, max_iter: int = 20,
                   tol: float = 1e-4, seed: int = 0) -> np.ndarray:
    """K-means with the Dataset task structure: one partial-sum task per
    Subset + a binary reduction tree per iteration (paper Fig. 3)."""
    rng = np.random.default_rng(seed)
    all_rows = ds.collect()
    centers = _kmeanspp_init(all_rows, n_clusters, rng)
    for _ in range(max_iter):
        def partial(x, centers=centers):
            d = ((x[:, None, :] - centers[None, :, :]) ** 2).sum(-1)
            lab = d.argmin(1)
            oh = np.eye(n_clusters, dtype=x.dtype)[lab]
            return np.concatenate([oh.T @ x, oh.sum(0)[:, None]], axis=1)

        partials = ds.map_subsets(partial)
        tot = ds.reduce(partials, np.add)
        sums, counts = tot[:, :-1], tot[:, -1]
        new = np.where(counts[:, None] > 0, sums / np.maximum(counts, 1)[:, None],
                       centers)
        shift = float(np.sqrt(((new - centers) ** 2).sum()))
        centers = new
        if shift < tol:
            break
    return centers
