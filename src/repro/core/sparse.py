"""Sparse (BCOO-backed) block storage for ds-arrays.

The paper's ds-array stores each block as EITHER a NumPy array or a
scipy.sparse CSR matrix, and the whole NumPy-like API keeps working over
both — that is what lets dislib run CSVM on datasets whose dense form would
not fit the cluster.  The TPU-native analogue used here keeps the stacked
layout of ``core.dsarray`` but swaps the rank-4 dense tensor for a single
``jax.experimental.sparse.BCOO`` with ``n_batch=2``:

* batch dims (gn, gm)      <->  the block grid (paper: list of lists)
* sparse dims (bn, bm)     <->  element-sparse storage inside each block
                                 (paper: one CSR matrix per block)
* ``nse``                  <->  max nnz per block; short blocks pad with
                                 out-of-bounds zero-data slots (dropped by
                                 every BCOO op)

A ds-array's storage is named by ``DsArray.block_format``:

* ``"dense"`` — the rank-4 stacked tensor (default, unchanged);
* ``"bcoo"``  — the stacked BCOO above.

Pad-state semantics: a BCOO block simply has **no entry** in the pad
region — construction (``tosparse``/``random_sparse``/``from_scipy``) masks
pad positions out — so sparse arrays are ZERO-padded *by construction*,
``ensure_zero_pad`` is the identity and remask elision is free.  Every
sparse-producing op below preserves that invariant (data maps are gated on
``fn(0) == 0``); ops that cannot, densify first.

Op policy (see the ``core.dsarray`` docstring for the full table):

* **sparse-native** — scalar scale/neg/abs/sqrt (index-preserving data
  maps), sparse±sparse and sparse*sparse (index merge), sparse*dense and
  sparse/dense (index-gather of the dense operand), ``astype``,
  ``transpose`` (batch-dim swap + index swap), grid padding, ``sum``
  (``bcoo_reduce_sum``), ``sp @ dense`` and ``spᵀ @ dense`` (one
  ``bcoo_dot_general`` per contraction — the sparse operand is **never**
  densified, asserted on the jaxpr in ``tests/test_sparse.py``);
* **densifying** — anything that breaks the implicit-zero algebra
  (``+ scalar``, ``exp``, dense/sp division), max/min reductions (implicit
  zeros compete), and the structural ops (slice/rechunk/concat/shuffle),
  which lower through the dense block-native kernels after ``todense()``.

The decision logic is shared by the eager dispatch (``binary``/
``map_blocks_sparse``) and the lazy facade (``core.expr`` records the same
classification, so a sparse ``Blockwise`` never silently densifies).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse
from jax.experimental.sparse import BCOO

from repro.core.blocking import BlockGrid, ceil_div

Number = Union[int, float]

FORMAT_DENSE = "dense"
FORMAT_BCOO = "bcoo"


def is_bcoo(x) -> bool:
    return isinstance(x, BCOO)


def _rebuild(ref: BCOO, data: jnp.ndarray,
             indices: Optional[jnp.ndarray] = None) -> BCOO:
    """BCOO with ``ref``'s index structure and new ``data`` (index-preserving
    data map).  Sorted/unique flags carry over: the indices are untouched."""
    return BCOO((data, ref.indices if indices is None else indices),
                shape=ref.shape, indices_sorted=ref.indices_sorted,
                unique_indices=ref.unique_indices)


def _canon_unique(sp: BCOO) -> BCOO:
    """``sp`` with duplicate indices merged (same capacity, jittable).

    Index-merge ops (sparse ± sparse) CONCATENATE entry lists, so a stored
    position may be split across several slots; a NONLINEAR data map over
    split entries is wrong (``|d1 + d2| != |d1| + |d2|``).  Every nonlinear
    data-map consumer routes through this; linear maps (scale, gather-mul)
    distribute over the split and skip it.
    """
    if sp.unique_indices:
        return sp
    return jsparse.bcoo_sum_duplicates(sp, nse=sp.nse)


_LINEAR_DATA_OPS = {"multiply", "divide"}


def _gather_dense_at(sp: BCOO, dense_blocks: jnp.ndarray) -> jnp.ndarray:
    """The dense stacked tensor's values at ``sp``'s stored positions.

    Advanced indexing with the batch iotas + stored indices emits one
    gather of shape (gn, gm, nse); out-of-bounds pad slots clamp (their data
    is zero so the gathered value is irrelevant).
    """
    gn, gm, bn, bm = sp.shape
    ii = jnp.minimum(sp.indices[..., 0], bn - 1)
    jj = jnp.minimum(sp.indices[..., 1], bm - 1)
    bi = jax.lax.broadcasted_iota(jnp.int32, ii.shape, 0)
    bj = jax.lax.broadcasted_iota(jnp.int32, ii.shape, 1)
    return dense_blocks[bi, bj, ii, jj]


# ---------------------------------------------------------------------------
# Conversions
# ---------------------------------------------------------------------------


def _pack_coo_arrays(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                     cell: np.ndarray, n_cells: int, bn: int, bm: int,
                     nse: Optional[int] = None, check_nse: bool = True,
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Bucket block-sorted COO triplets into ``(data, indices)`` host arrays
    of shape ``(n_cells, nse)`` / ``(n_cells, nse, 2)`` (pure NumPy: no XLA
    program per geometry).  ``cell`` is non-decreasing; ``rows``/``cols``
    are block-local.  Short cells pad with the out-of-bounds (bn, bm)
    sentinel and zero data.  With ``check_nse`` an explicit capacity below
    the real max cell nnz raises instead of silently dropping entries;
    pre-checked hot paths (the serve batcher) opt out.
    """
    counts = np.bincount(cell, minlength=n_cells)
    maxn = int(counts.max()) if counts.size else 0
    if nse is None:
        nse = maxn
    nse = max(1, int(nse))
    if check_nse and maxn > nse:
        raise ValueError(
            f"nse={nse} cannot hold the densest block ({maxn} nnz); "
            f"entries would be silently dropped.  Pass nse>=max_block_nnz "
            f"or check_nse=False if the capacity was already verified.")
    data = np.zeros((n_cells, nse), dtype=vals.dtype)
    indices = np.full((n_cells, nse, 2), (bn, bm), dtype=np.int32)
    slot = np.arange(len(cell)) - np.repeat(
        np.concatenate([[0], np.cumsum(counts)[:-1]]), counts)
    keep = slot < nse                  # unchecked explicit nse may truncate
    cell, slot = cell[keep], slot[keep]
    data[cell, slot] = vals[keep]
    indices[cell, slot, 0] = rows[keep]
    indices[cell, slot, 1] = cols[keep]
    return data, indices


def _pack_coo(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
              cell: np.ndarray, gn: int, gm: int, bn: int, bm: int,
              nse: Optional[int] = None, check_nse: bool = False) -> BCOO:
    """Bucket block-sorted COO triplets into the stacked BCOO (see
    :func:`_pack_coo_arrays`).  ``cell`` = gi*gm + gj."""
    data, indices = _pack_coo_arrays(rows, cols, vals, cell, gn * gm,
                                     bn, bm, nse, check_nse)
    nse = data.shape[1]
    return BCOO((jnp.asarray(data.reshape(gn, gm, nse)),
                 jnp.asarray(indices.reshape(gn, gm, nse, 2))),
                shape=(gn, gm, bn, bm), indices_sorted=True,
                unique_indices=True)


def tosparse(a: "DsArray", nse: Optional[int] = None) -> "DsArray":
    """Dense ds-array -> BCOO-blocked ds-array (identity if already sparse).

    The pad region is forced to zero first, so no pad position owns an
    entry — the sparse pad invariant holds by construction.  ``nse`` caps
    stored entries per block (default: the max block nnz).  Concrete arrays
    convert on the host in NumPy (``BCOO.fromdense`` compiles a fresh XLA
    program per geometry — ~1s each, which a test corpus or a data-loading
    loop over many shapes cannot afford); traced arrays (the lazy
    ``ToSparse`` node under jit) take the fromdense path.
    """
    from repro.core.dsarray import DsArray, PAD_ZERO
    if a.block_format == FORMAT_BCOO:
        return a
    me = a.ensure_zero_pad()
    if isinstance(me.blocks, jax.core.Tracer) or \
            jax.default_backend() != "cpu":
        blocks = BCOO.fromdense(me.blocks, n_batch=2, nse=nse)
        return DsArray(blocks, a.grid, PAD_ZERO)
    host = np.asarray(me.blocks)
    gn, gm, bn, bm = host.shape
    gi, gj, rr, cc = np.nonzero(host)          # C-order: grouped by block
    blocks = _pack_coo(rr.astype(np.int32), cc.astype(np.int32),
                       host[gi, gj, rr, cc], gi * gm + gj,
                       gn, gm, bn, bm, nse)
    return DsArray(blocks, a.grid, PAD_ZERO)


def todense(a: "DsArray") -> "DsArray":
    """BCOO-blocked ds-array -> dense (identity if already dense).  Stored
    entries scatter into a zero tensor, so the result pad is exactly zero.
    Concrete CPU arrays scatter on the host (``BCOO.todense`` compiles one
    XLA program per geometry); traced / accelerator-resident arrays keep
    the compiled path."""
    from repro.core.dsarray import DsArray, PAD_ZERO
    if a.block_format == FORMAT_DENSE:
        return a
    sp = a.blocks
    if isinstance(sp.data, jax.core.Tracer) or jax.default_backend() != "cpu":
        return DsArray(sp.todense(), a.grid, PAD_ZERO)
    gn, gm, bn, bm = sp.shape
    data = np.asarray(sp.data)
    idx = np.asarray(sp.indices)
    host = np.zeros((gn, gm, bn, bm), data.dtype)
    bi = np.broadcast_to(np.arange(gn)[:, None, None], data.shape)
    bj = np.broadcast_to(np.arange(gm)[None, :, None], data.shape)
    ok = (idx[..., 0] < bn) & (idx[..., 1] < bm)     # drop OOB pad slots
    np.add.at(host, (bi[ok], bj[ok],                 # add: duplicates merge
                     idx[..., 0][ok], idx[..., 1][ok]), data[ok])
    return DsArray(jnp.asarray(host), a.grid, PAD_ZERO)


def density(a: "DsArray") -> float:
    """nnz / logical size (concrete arrays only)."""
    n, m = a.shape
    if a.block_format == FORMAT_BCOO:
        nnz = int(jnp.count_nonzero(a.blocks.data))
    else:
        nnz = int(jnp.count_nonzero(a.ensure_zero_pad().blocks))
    return nnz / max(1, n * m)


def canonicalize(a: "DsArray", nse: Optional[int] = None) -> "DsArray":
    """Re-pack a sparse ds-array: merge duplicate indices (left behind by
    sparse+sparse index concatenation) and shrink ``nse`` back to the max
    block nnz.  Eager-only (the output nse is data-dependent)."""
    from repro.core.dsarray import DsArray, PAD_ZERO
    if a.block_format != FORMAT_BCOO:
        return a
    blocks = jsparse.bcoo_sum_duplicates(a.blocks, nse=nse)
    return DsArray(blocks, a.grid, PAD_ZERO)


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------


def random_sparse(key, shape: Tuple[int, int], block_shape: Tuple[int, int],
                  density: float = 0.01, dtype=jnp.float32,
                  distribution: str = "normal") -> "DsArray":
    """Random BCOO-blocked ds-array: ``density`` fraction of entries per
    block hold samples, the rest are implicit zeros (paper §4.2.2 per-block
    creation, sparse edition).  Pad positions of edge blocks are zeroed so
    the sparse pad invariant holds."""
    from repro.core.dsarray import DsArray, PAD_ZERO
    grid = BlockGrid(tuple(shape), tuple(block_shape))
    gn, gm, bn, bm = grid.stacked_shape
    gen = {"normal": jax.random.normal, "uniform": jax.random.uniform}[distribution]
    sp = jsparse.random_bcoo(key, (gn, gm, bn, bm), nse=float(density),
                             n_batch=2, dtype=jnp.dtype(dtype), generator=gen)
    n, m = shape
    if gn * bn > n or gm * bm > m:
        # zero (not drop) entries landing in the pad region: indices keep
        # their static shape, zero data is an explicit zero — still valid
        bi = jax.lax.broadcasted_iota(jnp.int32, sp.data.shape, 0)
        bj = jax.lax.broadcasted_iota(jnp.int32, sp.data.shape, 1)
        valid = ((bi * bn + sp.indices[..., 0]) < n) & \
                ((bj * bm + sp.indices[..., 1]) < m)
        sp = _rebuild(sp, jnp.where(valid, sp.data, jnp.zeros((), sp.dtype)))
    return DsArray(sp, grid, PAD_ZERO)


def from_scipy(mat, block_shape: Tuple[int, int],
               nse: Optional[int] = None,
               check_nse: bool = True) -> "DsArray":
    """scipy.sparse matrix -> BCOO-blocked ds-array, without densifying.

    The paper loads CSVM datasets straight into CSR-blocked ds-arrays; here
    the COO triplets are bucketed by block (pure NumPy index math, touching
    only the nnz entries) and packed into the stacked BCOO with ``nse`` =
    the max block nnz.  An explicit ``nse`` fixes the stored-entry capacity
    instead: the serving layer packs every request batch of one geometry
    bucket at the bucket's declared capacity, which keeps the plan-cache
    leaf signature — and therefore the compiled program — identical across
    batches with different nnz.  An explicit ``nse`` below the real max
    block nnz raises ``ValueError`` (the bincount guard costs O(nnz));
    pre-checked hot paths that already compared :func:`max_block_nnz`
    against the capacity pass ``check_nse=False`` to skip the raise.
    """
    from repro.core.dsarray import DsArray, PAD_ZERO
    coo = mat.tocoo()
    coo.sum_duplicates()
    n, m = coo.shape
    grid = BlockGrid((n, m), tuple(block_shape))
    gn, gm, bn, bm = grid.stacked_shape
    cell = (coo.row // bn) * gm + coo.col // bm
    order = np.argsort(cell, kind="stable")
    blocks = _pack_coo((coo.row[order] % bn).astype(np.int32),
                       (coo.col[order] % bm).astype(np.int32),
                       coo.data[order], cell[order], gn, gm, bn, bm, nse,
                       check_nse=check_nse)
    return DsArray(blocks, grid, PAD_ZERO)


def max_block_nnz(mat, block_shape: Tuple[int, int]) -> int:
    """Max nnz of any block of ``mat`` under ``block_shape`` (host-side
    NumPy over the stored triplets only) — the guard a fixed-capacity
    :func:`from_scipy` pack needs: a batch whose densest block exceeds the
    declared bucket ``nse`` must fall back rather than truncate."""
    coo = mat.tocoo()
    coo.sum_duplicates()
    if coo.nnz == 0:
        return 0
    n, m = coo.shape
    grid = BlockGrid((n, m), tuple(block_shape))
    gn, gm, bn, bm = grid.stacked_shape
    cell = (coo.row // bn) * gm + coo.col // bm
    return int(np.bincount(cell, minlength=gn * gm).max())


class StackedBCOOBuilder:
    """Incremental stacked-BCOO assembly, one block row at a time.

    The streaming svmlight loader hands each completed block row's COO
    triplets here; they are bucketed by block column with the same pure
    NumPy pack as :func:`from_scipy` (sorted by (row, col) inside each
    block so ``indices_sorted`` holds) and moved to the device arena
    immediately — host memory stays O(one block row's triplets), never
    O(nnz of the file).

    With ``nse=None`` (default) each appended row packs at its own max
    block nnz and :meth:`finalize` pads every row up to the global max —
    bit-identical capacity to the :func:`from_scipy` default.  An explicit
    ``nse`` fixes the capacity up front and overflowing rows raise
    ``ValueError`` at append time (no silent truncation mid-stream).
    """

    def __init__(self, m: int, block_shape: Tuple[int, int],
                 dtype=np.float32, nse: Optional[int] = None):
        self.bn, self.bm = int(block_shape[0]), int(block_shape[1])
        self.m = int(m)
        self.gm = max(1, ceil_div(self.m, self.bm))
        self.dtype = np.dtype(dtype)
        self.nse = None if nse is None else max(1, int(nse))
        self.n_rows = 0                       # logical rows appended so far
        self._data: list = []                 # per block row: jnp (gm, nse_i)
        self._indices: list = []              # per block row: jnp (gm, nse_i, 2)

    def append_blockrow(self, rows: np.ndarray, cols: np.ndarray,
                        vals: np.ndarray, n_rows: int) -> None:
        """Add one block row from triplets (``rows`` block-local in
        [0, bn), ``cols`` global in [0, m), any order)."""
        if not 0 < n_rows <= self.bn:
            raise ValueError(f"n_rows={n_rows} outside (0, bn={self.bn}]")
        rows = np.asarray(rows, np.int32)
        cols = np.asarray(cols, np.int32)
        vals = np.asarray(vals, self.dtype)
        if cols.size and (int(cols.max()) >= self.m or int(cols.min()) < 0):
            raise ValueError(
                f"feature id {int(cols.max())} out of range for "
                f"n_features={self.m} (0-based after shift — a 0-based "
                f"file read as 1-based hits this)")
        cell = cols // self.bm
        # sort by (block, row, col) so indices_sorted holds (int64 key:
        # gm*bn*bm can pass 2**31)
        order = np.argsort(cell.astype(np.int64) * (self.bn * self.bm)
                           + rows.astype(np.int64) * self.bm + cols % self.bm,
                           kind="stable")
        data, indices = _pack_coo_arrays(
            rows[order], (cols % self.bm)[order], vals[order], cell[order],
            self.gm, self.bn, self.bm, nse=self.nse, check_nse=True)
        # copy=True: jnp.asarray would zero-copy an aligned host buffer,
        # RETAINING one host array per block row — O(file) host memory.
        # An owned device copy frees the host side immediately.
        self._data.append(jnp.array(data, copy=True))
        self._indices.append(jnp.array(indices, copy=True))
        self.n_rows += int(n_rows)

    def finalize(self) -> "DsArray":
        """Stack the appended block rows into a BCOO ds-array of shape
        ``(n_rows, m)``.  Per-row capacities pad up to the target nse on
        device (data pads with zeros, indices with the OOB sentinel, so
        sortedness and the pad invariant are preserved)."""
        from repro.core.dsarray import DsArray, PAD_ZERO
        if not self._data:
            raise ValueError("no block rows appended")
        target = self.nse if self.nse is not None else \
            max(1, max(d.shape[1] for d in self._data))
        sentinel = jnp.asarray([self.bn, self.bm], jnp.int32)
        data_rows, index_rows = [], []
        for d, ix in zip(self._data, self._indices):
            pad = target - d.shape[1]
            if pad:
                d = jnp.concatenate(
                    [d, jnp.zeros((self.gm, pad), d.dtype)], axis=1)
                ix = jnp.concatenate(
                    [ix, jnp.broadcast_to(sentinel, (self.gm, pad, 2))],
                    axis=1)
            data_rows.append(d)
            index_rows.append(ix)
        blocks = BCOO((jnp.stack(data_rows), jnp.stack(index_rows)),
                      shape=(len(data_rows), self.gm, self.bn, self.bm),
                      indices_sorted=True, unique_indices=True)
        grid = BlockGrid((self.n_rows, self.m), (self.bn, self.bm))
        if grid.stacked_shape[0] != len(data_rows):
            raise ValueError(
                f"appended {len(data_rows)} block rows but {self.n_rows} "
                f"logical rows need {grid.stacked_shape[0]}")
        return DsArray(blocks, grid, PAD_ZERO)


def fetch_row_dense(a: "DsArray", i: int) -> jnp.ndarray:
    """Row ``i`` of a sparse ds-array as a padded dense ``(gm*bm,)`` vector.

    Touches only block row ``i // bn`` (its entries scatter-add into the
    output), never the whole array — the k-means++ seeding fetch.
    """
    gn, gm, bn, bm = a.blocks.shape
    gi, off = int(i) // bn, int(i) % bn
    data = a.blocks.data[gi]                       # (gm, nse)
    idx = a.blocks.indices[gi]                     # (gm, nse, 2)
    bj = jax.lax.broadcasted_iota(jnp.int32, data.shape, 0)
    col = bj * bm + jnp.minimum(idx[..., 1], bm - 1)
    hit = (idx[..., 0] == off) & (idx[..., 1] < bm)
    vals = jnp.where(hit, data, jnp.zeros((), data.dtype))
    return jnp.zeros((gm * bm,), data.dtype).at[col.ravel()].add(vals.ravel())


# ---------------------------------------------------------------------------
# Elementwise dispatch (shared by the eager ops and the lazy recorder)
# ---------------------------------------------------------------------------


def _probe_zero(op: Callable, rhs, reverse: bool, dtype) -> bool:
    """True iff ``op`` maps an implicit zero (paired with the known scalar
    ``rhs``) back to zero — the gate for index-preserving data maps."""
    try:
        z = jnp.zeros((), dtype)
        out = op(rhs, z) if reverse else op(z, rhs)
        return bool(np.asarray(out) == 0)
    except Exception:
        return False


_PAIR_NATIVE = {"add", "subtract", "multiply"}
_GATHER_NATIVE = {"multiply", "divide"}


def classify_binary(op: Callable, lhs_sparse: bool, rhs, reverse: bool,
                    lhs_dtype) -> str:
    """How to execute ``op(lhs, rhs)`` with at least one sparse operand.

    ``rhs`` is ``("ds", is_sparse, dtype)`` or a raw scalar.  Returns:

    * ``"data"``   — index-preserving map over the sparse operand's data
      (scalar other, ``op(0, s) == 0``);
    * ``"pair"``   — both sparse: BCOO index-merge add/sub/mul;
    * ``"gather"`` — sparse x dense mul/div with the SPARSE side as the
      left numerator: gather the dense operand at the stored indices;
    * ``"dense"``  — no zero-preserving sparse form: densify first.
    """
    name = getattr(op, "__name__", "")
    if isinstance(rhs, tuple):
        _, rhs_sparse, _ = rhs
        if lhs_sparse and rhs_sparse:
            return "pair" if name in _PAIR_NATIVE else "dense"
        if not (lhs_sparse or rhs_sparse):
            # both dense: alignment can densify a sparse operand (a
            # block-shape mismatch rechunks, and rechunk densifies by
            # policy) — nothing sparse is left for gather to index
            return "dense"
        # exactly one side sparse; the gather form needs op(0, y) == 0 for
        # EVERY y, so only mul (0*y) and div with the sparse side on top
        sparse_on_top = lhs_sparse != reverse
        if name in _GATHER_NATIVE and (name == "multiply" or sparse_on_top):
            return "gather"
        return "dense"
    return "data" if (lhs_sparse and _probe_zero(op, rhs, reverse, lhs_dtype)) \
        else "dense"


def data_map_fn(op: Callable, scalar, reverse: bool) -> Callable:
    """blocks->blocks closure for a scalar data map (used by the lazy
    Blockwise recorder as well as the eager path)."""
    linear = getattr(op, "__name__", "") in _LINEAR_DATA_OPS

    def fn(sp: BCOO) -> BCOO:
        if not linear:
            sp = _canon_unique(sp)
        out = op(scalar, sp.data) if reverse else op(sp.data, scalar)
        return _rebuild(sp, out)
    return fn


def _multiply_sparse(a: BCOO, b: BCOO) -> BCOO:
    """Elementwise ``a * b`` of two stacked BCOO tensors of one shape, in
    ``a``'s index structure and capacity.

    ``jsparse.bcoo_multiply_sparse`` compares every stored entry of a block
    with every entry of the other and returns ``nse_a * nse_b`` slots: for
    an rcv1-sized block (~10^5 entries) that is hundreds of GB.  Here both
    entry lists of each block are sorted together by (row, col, operand),
    so an entry of ``a`` is directly followed by its partner in ``b`` when
    it has one; duplicates are merged first, so it has at most one.
    """
    a, b = _canon_unique(a), _canon_unique(b)
    dtype = jnp.result_type(a.dtype, b.dtype)
    ax = a.data.ndim - 1                                 # the entry axis

    def cat(u, v):
        return jnp.concatenate([u, v], axis=ax)

    def succ(v):                    # each slot's successor (the last: itself)
        return jnp.concatenate([v[..., 1:], v[..., -1:]], axis=ax)

    rows = cat(a.indices[..., 0], b.indices[..., 0])
    cols = cat(a.indices[..., 1], b.indices[..., 1])
    side = cat(jnp.zeros(a.data.shape, jnp.int32),
               jnp.ones(b.data.shape, jnp.int32))
    data = cat(a.data.astype(dtype), b.data.astype(dtype))
    slot = jax.lax.broadcasted_iota(jnp.int32, rows.shape, ax)
    rows, cols, side, data, slot = jax.lax.sort(
        (rows, cols, side, data, slot), dimension=ax, num_keys=3)
    hit = ((side == 0) & (succ(side) == 1) & (rows == succ(rows))
           & (cols == succ(cols)))
    prod = jnp.where(hit, data * succ(data), jnp.zeros((), dtype))
    _, prod = jax.lax.sort((slot, prod), dimension=ax, num_keys=1)
    return _rebuild(a, prod[..., : a.nse])


def pair_fn(op: Callable, reverse: bool) -> Callable:
    """blocks->blocks closure for sparse (+|-|*) sparse."""
    name = getattr(op, "__name__", "")

    def fn(x: BCOO, y: BCOO) -> BCOO:
        a, b = (y, x) if reverse else (x, y)
        if name == "multiply":
            return _multiply_sparse(a, b)
        # jnp ufuncs reject BCOO; the operator forms concatenate indices
        return a + b if name == "add" else a - b
    return fn


def gather_fn(op: Callable, sparse_left: bool) -> Callable:
    """blocks->blocks closure for sparse x dense mul/div: the dense operand
    is gathered at the sparse operand's stored indices, so the result keeps
    the index structure and the dense block tensor is read once."""
    def fn(x, y):
        sp, dn = (x, y) if sparse_left else (y, x)
        vals = _gather_dense_at(sp, dn)
        out = op(sp.data, vals) if sparse_left else op(vals, sp.data)
        # zero-data slots (pad sentinels, grid-growth fillers) must stay
        # EXACTLY zero — 0/0 at a dirty dense pad position would smuggle a
        # nan into the pad region and break the construction invariant
        out = jnp.where(sp.data == 0, jnp.zeros((), out.dtype), out)
        return _rebuild(sp, out)
    return fn


def binary(a: "DsArray", other, op: Callable, reverse: bool):
    """Eager sparse-aware ``_binary``: operands are aligned exactly like the
    dense path, then dispatched per :func:`classify_binary`.  Returns
    NotImplemented for operand types the dense path also rejects."""
    from repro.core.dsarray import DsArray, PAD_ZERO
    me = a
    if isinstance(other, DsArray):
        if other.shape != me.shape:
            raise ValueError(f"shape mismatch {me.shape} vs {other.shape}")
        if other.block_shape != me.block_shape:
            other = other.rechunk(me.block_shape)      # densifies a sparse rhs
        if other.stacked_grid != me.stacked_grid:
            common = (max(me.stacked_grid[0], other.stacked_grid[0]),
                      max(me.stacked_grid[1], other.stacked_grid[1]))
            me, other = me._pad_grid_to(common), other._pad_grid_to(common)
        rhs_desc = ("ds", other.block_format == FORMAT_BCOO, other.dtype)
    elif isinstance(other, (int, float, jnp.ndarray, np.ndarray)) \
            and jnp.ndim(other) == 0:
        if isinstance(other, jax.core.Tracer):
            return todense(me)._binary(other, op, reverse)
        rhs_desc = other
    else:
        return NotImplemented

    mode = classify_binary(op, me.block_format == FORMAT_BCOO, rhs_desc,
                           reverse, me.dtype)
    if mode == "data":
        return DsArray(data_map_fn(op, other, reverse)(me.blocks), me.grid,
                       PAD_ZERO)
    if mode == "pair":
        return DsArray(pair_fn(op, reverse)(me.blocks, other.blocks),
                       me.grid, PAD_ZERO)
    if mode == "gather":
        lhs_sp = me.block_format == FORMAT_BCOO
        x, y = (me.blocks, other.blocks)
        out = gather_fn(op if not reverse else (lambda u, v: op(v, u)),
                        lhs_sp)(x, y)
        return DsArray(out, me.grid, PAD_ZERO)
    # densify whichever operands are sparse and take the dense path
    me = todense(me)
    if isinstance(other, DsArray):
        other = todense(other)
    return me._binary(other, op, reverse)


def zero_preserving_map(fn: Callable, dtype) -> bool:
    """Probe ``fn`` on a zero block (like the dense pad probe): data-map
    eligible iff it is shape-preserving and maps zero to zero."""
    try:
        probe = jnp.zeros((1, 1, 1, 1), dtype)
        out = fn(probe)
        return (not isinstance(out, jax.core.Tracer)
                and getattr(out, "shape", None) == (1, 1, 1, 1)
                and bool(np.asarray(out).item() == 0))
    except Exception:
        return False


def map_blocks_sparse(a: "DsArray", fn: Callable, pad) -> "DsArray":
    """``map_blocks`` over a sparse ds-array.

    Zero-preserving elementwise fns (probed on a zero block, like the dense
    pad probe) run as an index-preserving data map — the data vector is
    viewed as rank-4 ``(gn, gm, nse, 1)`` so fns written against block
    tensors see the rank they expect.  Anything else (``fn(0) != 0``,
    position-dependent fns flagged with an explicit ``pad``) densifies.
    """
    from repro.core.dsarray import DsArray, PAD_ZERO
    if pad is not None or not zero_preserving_map(fn, a.dtype):
        return todense(a).map_blocks(fn, pad=pad)
    return DsArray(sparse_map_fn(fn)(a.blocks), a.grid, PAD_ZERO)


def sparse_map_fn(fn: Callable) -> Callable:
    """blocks->blocks closure of the data-map above (for the lazy layer).
    User fns are nonlinear until proven otherwise: merge split entries."""
    def mapped(sp: BCOO) -> BCOO:
        sp = _canon_unique(sp)
        return _rebuild(sp, fn(sp.data[..., None])[..., 0])
    return mapped


# ---------------------------------------------------------------------------
# Structure ops (sparse-native)
# ---------------------------------------------------------------------------


def aligned_slice_sparse(a: "DsArray", rows: slice, cols: slice) -> "DsArray":
    """Block-aligned slice of a BCOO-blocked ds-array, sparse-natively.

    A slice whose start sits on a block boundary (unit step) is a pure
    **batch-dim slice** of the stacked BCOO: ``data[g0:g1, h0:h1]`` /
    ``indices[g0:g1, h0:h1]`` — O(selected entries), no re-bucketing, and
    crucially no ``bcoo_todense`` (the ROADMAP PR-4 follow-on: this used to
    densify).  A slice that STOPS mid-block keeps the entry slots but zeroes
    the data of positions past the new logical edge — indices keep their
    static shape, zero data is an explicit zero (the same trick
    ``random_sparse`` uses for pad entries), so the zero-pad-by-construction
    invariant holds on the result.

    This is the CSVM cascade's per-chunk row partition: chunks are batch
    slices of the one stacked BCOO, so the data matrix is never densified
    on the way into the per-node solvers.
    """
    from repro.core.dsarray import DsArray, PAD_ZERO
    from repro.core.blocking import grid_span
    sp = a.blocks
    gn, gm, bn, bm = sp.shape
    n, m = a.shape
    r0, r1, rs = rows.indices(n)
    c0, c1, cs = cols.indices(m)
    assert rs == 1 and cs == 1 and r0 % bn == 0 and c0 % bm == 0
    g0, g1 = (0, 1) if r1 <= r0 else grid_span(r0, r1, bn)
    h0, h1 = (0, 1) if c1 <= c0 else grid_span(c0, c1, bm)
    data = sp.data[g0:g1, h0:h1]
    indices = sp.indices[g0:g1, h0:h1]
    nr, nc = max(0, r1 - r0), max(0, c1 - c0)
    # zero entries past the new logical edge (slice stopped mid-block, or an
    # empty selection kept its one placeholder block)
    if (g1 - g0) * bn > nr or (h1 - h0) * bm > nc:
        bi = jax.lax.broadcasted_iota(jnp.int32, data.shape, 0)
        bj = jax.lax.broadcasted_iota(jnp.int32, data.shape, 1)
        valid = ((bi * bn + indices[..., 0]) < nr) & \
                ((bj * bm + indices[..., 1]) < nc)
        data = jnp.where(valid, data, jnp.zeros((), data.dtype))
    blocks = BCOO((data, indices), shape=(g1 - g0, h1 - h0, bn, bm),
                  indices_sorted=sp.indices_sorted,
                  unique_indices=sp.unique_indices)
    from repro.core.structural import preserve_sharding
    out = DsArray(blocks, BlockGrid((nr, nc), (bn, bm)), PAD_ZERO)
    return preserve_sharding(out, sp.data)


def rows_to_dense(a: "DsArray") -> np.ndarray:
    """All rows of a (small) sparse ds-array as one dense ``(n, m)`` host
    array: stored entries scatter-add straight into row-major layout.

    This is the CSVM per-node basis extraction — the (s, m) dense sub-problem
    matrix every kernel-SVM solver materializes (libsvm's kernel cache does
    the same) — built from the BCOO's triplets in O(nnz) NumPy, never
    through ``todense()`` (which would build the stacked dense tensor and
    compile an XLA scatter per geometry).  Dense inputs take ``collect``.
    """
    if a.block_format != FORMAT_BCOO:
        return np.asarray(a.collect())
    sp = a.blocks
    gn, gm, bn, bm = sp.shape
    n, m = a.shape
    data = np.asarray(sp.data)
    idx = np.asarray(sp.indices)
    out = np.zeros((gn * bn, gm * bm), data.dtype)
    rr = (np.arange(gn)[:, None, None] * bn +
          np.minimum(idx[..., 0], bn - 1))
    cc = (np.arange(gm)[None, :, None] * bm +
          np.minimum(idx[..., 1], bm - 1))
    ok = (idx[..., 0] < bn) & (idx[..., 1] < bm)      # drop OOB pad slots
    np.add.at(out, (rr[ok], cc[ok]), data[ok])        # add: duplicates merge
    return out[:n, :m]


def astype_sparse(a: "DsArray", dtype) -> "DsArray":
    from repro.core.dsarray import DsArray, PAD_ZERO
    # merge split entries first: cast(d1 + d2) != cast(d1) + cast(d2) for
    # narrowing casts, and the dense path casts the SUMMED value
    sp = _canon_unique(a.blocks)
    return DsArray(_rebuild(sp, sp.data.astype(dtype)), a.grid, PAD_ZERO)


def transpose_sparse(a: "DsArray") -> "DsArray":
    """Batch-dim swap + per-entry index swap: no dense relayout, the HBM
    traffic is O(nnz) instead of O(dense)."""
    from repro.core.dsarray import DsArray, PAD_ZERO
    return DsArray(a.blocks.transpose((1, 0, 3, 2)), a.grid.transpose(),
                   PAD_ZERO)


def pad_grid_sparse(a: "DsArray", stacked_grid: Tuple[int, int]) -> "DsArray":
    """Grow the stacked grid: new blocks get zero-data slots at index (0, 0)
    — explicit zeros, which every consumer treats as absent."""
    from repro.core.dsarray import DsArray, PAD_ZERO
    gn, gm = a.stacked_grid
    tn, tm = stacked_grid
    if (tn, tm) == (gn, gm):
        return a
    if tn < gn or tm < gm:
        raise ValueError("can only grow the stacked grid")
    sp = a.blocks
    data = jnp.pad(sp.data, ((0, tn - gn), (0, tm - gm), (0, 0)))
    indices = jnp.pad(sp.indices, ((0, tn - gn), (0, tm - gm), (0, 0), (0, 0)))
    blocks = BCOO((data, indices), shape=(tn, tm) + sp.shape[2:])
    return DsArray(blocks, a.grid, PAD_ZERO)


def reduce_sparse(a: "DsArray", op: str, axis: Optional[int]):
    """Reductions over a sparse ds-array.

    ``sum`` is sparse-native: ``bcoo_reduce_sum`` folds the stored entries
    (implicit zeros are the identity) — the sparse operand is never
    densified; only the small reduced result is.  ``max``/``min`` must rank
    stored entries against the implicit zeros, so they take the dense path.
    """
    from repro.core.dsarray import DsArray, pad_state_of
    if op != "sum":
        return todense(a)._reduce(op, axis)
    sp = a.blocks
    if axis is None:
        return jsparse.bcoo_reduce_sum(sp, axes=(0, 1, 2, 3)).todense()
    if axis == 0:
        out = jsparse.bcoo_reduce_sum(sp, axes=(0, 2)).todense()  # (gm, bm)
        gm, bm = out.shape
        blocks = out.reshape(1, gm, 1, bm)
        grid = BlockGrid((1, a.shape[1]), (1, bm))
    elif axis == 1:
        out = jsparse.bcoo_reduce_sum(sp, axes=(1, 3)).todense()  # (gn, bn)
        gn, bn = out.shape
        blocks = out.reshape(gn, 1, bn, 1)
        grid = BlockGrid((a.shape[0], 1), (bn, 1))
    else:
        raise ValueError(f"axis must be 0, 1 or None, got {axis}")
    return DsArray(blocks, grid, pad_state_of(0))


def distribute_sparse(a: "DsArray", mesh, axes) -> "DsArray":
    """Shard a sparse ds-array's batch (grid) dims over the mesh: data and
    indices are placed leaf-by-leaf with matching specs."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.dsarray import DsArray
    from repro.core.blocking import round_up
    dn = mesh.shape[axes[0]] if axes[0] else 1
    dm = mesh.shape[axes[1]] if axes[1] else 1
    gn, gm = a.stacked_grid
    padded = pad_grid_sparse(a, (round_up(gn, dn), round_up(gm, dm)))
    sp = padded.blocks
    data = jax.device_put(sp.data, NamedSharding(mesh, P(axes[0], axes[1], None)))
    indices = jax.device_put(
        sp.indices, NamedSharding(mesh, P(axes[0], axes[1], None, None)))
    return DsArray(BCOO((data, indices), shape=sp.shape), a.grid,
                   padded.pad_state)


# ---------------------------------------------------------------------------
# Invariant checking (the differential harness + REPRO_DEBUG=1 validator)
# ---------------------------------------------------------------------------


def check_bcoo_invariants(a: "DsArray") -> None:
    """Raise if the BCOO storage violates the sparse ds-array contract:
    non-negative indices; any out-of-bounds slot carries zero data (the pad
    sentinel); any in-bounds entry at a logical-pad position carries zero
    data (sparse arrays are zero-padded by construction)."""
    sp = a.blocks
    if sp.n_batch != 2 or sp.n_dense != 0:
        raise AssertionError(
            f"sparse blocks must be n_batch=2/n_dense=0 BCOO, got "
            f"n_batch={sp.n_batch} n_dense={sp.n_dense}")
    if a.pad_state.kind != "zero":
        raise AssertionError(
            f"sparse ds-arrays are zero-padded by construction, "
            f"claimed {a.pad_state}")
    idx = np.asarray(sp.indices)
    data = np.asarray(sp.data)
    gn, gm, bn, bm = sp.shape
    n, m = a.shape
    def _site(mask) -> str:
        gi, gj, slot = (int(v) for v in np.argwhere(mask)[0])
        return (f"{int(mask.sum())} violation(s), first in block "
                f"({gi}, {gj}) slot {slot}: index "
                f"({int(idx[gi, gj, slot, 0])}, {int(idx[gi, gj, slot, 1])})"
                f", data {data[gi, gj, slot]!r}")

    neg = (idx[..., 0] < 0) | (idx[..., 1] < 0)
    if np.any(neg):
        raise AssertionError(f"negative BCOO index: {_site(neg)}")
    oob = (idx[..., 0] >= bn) | (idx[..., 1] >= bm)
    bad = oob & (data != 0)
    if np.any(bad):
        raise AssertionError(
            f"out-of-bounds BCOO slot with nonzero data: {_site(bad)}")
    bi = np.arange(gn)[:, None, None]
    bj = np.arange(gm)[None, :, None]
    in_pad = ((bi * bn + idx[..., 0]) >= n) | ((bj * bm + idx[..., 1]) >= m)
    bad = in_pad & ~oob & (data != 0)
    if np.any(bad):
        raise AssertionError(
            "nonzero BCOO entry in the logical pad region "
            f"(sparse pad invariant violated): {_site(bad)}")
