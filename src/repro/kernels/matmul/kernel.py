"""Tiled MXU matmul kernels — the local GEMM under every ds-array ``@``.

Two entry points:

* ``matmul_padded`` — dense 2-D ``(m, k) @ (k, n)`` on pre-padded shapes.
* ``stacked_matmul`` — the ds-array-native form: consumes the stacked block
  tensors ``(gi, gk, bn, bk) x (gk, gj, bk, bm)`` directly, grid dims as
  Pallas grid dims, so the distributed ``@`` lowers into ONE kernel launch
  with no relayout.

Both compute C one VMEM tile at a time; the whole K reduction (grid-k and
block-k) runs as the innermost (sequential) grid dimension with an fp32
accumulator tile resident in VMEM, so each C tile is written to HBM exactly
once.  Tile sizes default to 512³ fp32-equivalents; dims should be multiples
of 128 to keep the MXU systolic array full (the ops.py wrappers pad 2-D
shapes / fall back to einsum for non-MXU block shapes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _stacked_matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int,
                           transpose_a: bool = False):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_tile = a_ref[0, 0].T if transpose_a else a_ref[0, 0]
    acc_ref[...] += jnp.dot(
        a_tile, b_ref[0, 0], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


def _pick_tile(dim: int, target: int, unit: int) -> int:
    """Tile for one block dim: the whole dim when it is at most ``target``,
    else the largest divisor of ``dim`` that is a multiple of ``unit`` and at
    most ``target``.  ``unit`` is 8 for a dim on the sublanes of its tiles and
    128 for one on the lanes: Mosaic accepts a tile dim that is a multiple of
    those or the whole array dim.  Only a dim with no such divisor is taken
    whole; a whole 10,000-row block would overflow the scoped VMEM."""
    if dim <= target:
        return dim
    for t in range(target - target % unit, 0, -unit):
        if dim % t == 0:
            return t
    return dim


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret",
                     "transpose_a"),
)
def stacked_matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_m: int = 512,
    block_n: int = 512,
    block_k: int = 512,
    out_dtype=None,
    interpret: bool = False,
    transpose_a: bool = False,
) -> jnp.ndarray:
    """Fused GEMM directly on stacked ds-array block tensors.

    ``(gi, gk, bn, bk) x (gk, gj, bk, bm) -> (gi, gj, bn, bm)``: the ds-array
    grid dims become Pallas grid dims and the whole k reduction — grid-k
    times block-k — runs as the innermost (sequential) grid dimension with
    one fp32 accumulator tile resident in VMEM, so each C tile is written to
    HBM exactly once.  This replaces the per-grid-k Python loop of vmapped
    2-D kernels (O(gk) pallas_call launches, each re-reading and re-writing
    the full C partial) with a single launch and no HBM round-trips for
    partial sums.

    ``transpose_a=True`` computes ``Aᵀ @ B`` with ``a`` still in its
    UNtransposed stacked layout ``(gk, gi, bk, bn)``: the transpose is folded
    into the A-operand block-index map (grid dims swapped) plus an in-VMEM
    tile transpose fed to the MXU — the relayout of the full stacked tensor
    that an eager ``A.T`` would materialize in HBM never happens.

    Block dims larger than ``block_*`` are sub-tiled by the largest aligned
    divisor at most the target (``_pick_tile``), so a tile never outgrows
    the scoped VMEM whatever the block height.  ``interpret=True`` runs the
    same kernel off-TPU.
    """
    if transpose_a:
        gk, gi, bk, bn = a.shape
        gk2, gj, bk2, bm = b.shape
    else:
        gi, gk, bn, bk = a.shape
        gk2, gj, bk2, bm = b.shape
    if gk != gk2 or bk != bk2:
        raise ValueError(f"stacked matmul inner mismatch {a.shape} x {b.shape}")
    out_dtype = out_dtype or jnp.promote_types(a.dtype, b.dtype)
    # A's tile is (tm, tk), or (tk, tm) under transpose_a; B's is (tk, tn)
    tm = _pick_tile(bn, block_m, 128 if transpose_a else 8)
    tn = _pick_tile(bm, block_n, 128)
    tk = _pick_tile(bk, block_k, 8 if transpose_a else 128)
    fm, fn, fk = bn // tm, bm // tn, bk // tk
    grid = (gi * fm, gj * fn, gk * fk)
    if transpose_a:
        # A block (i, k) of Aᵀ lives at a[k, i] with dims (bk, bn): swap the
        # grid/sub-tile coordinates in the index map and transpose in VMEM
        a_spec = pl.BlockSpec((1, 1, tk, tm),
                              lambda i, j, k: (k // fk, i // fm, k % fk, i % fm))
    else:
        a_spec = pl.BlockSpec((1, 1, tm, tk),
                              lambda i, j, k: (i // fm, k // fk, i % fm, k % fk))
    return pl.pallas_call(
        functools.partial(_stacked_matmul_kernel, n_k=grid[2],
                          transpose_a=transpose_a),
        grid=grid,
        in_specs=[
            a_spec,
            pl.BlockSpec((1, 1, tk, tn),
                         lambda i, j, k: (k // fk, j // fn, k % fk, j % fn)),
        ],
        out_specs=pl.BlockSpec((1, 1, tm, tn),
                               lambda i, j, k: (i // fm, j // fn, i % fm, j % fn)),
        out_shape=jax.ShapeDtypeStruct((gi, gj, bn, bm), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)


def matmul_padded(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_m: int = 512,
    block_n: int = 512,
    block_k: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """C = A @ B for shapes already padded to block multiples."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    out_dtype = out_dtype or a.dtype
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
