"""Explicitly-scheduled collective implementations of ds-array ops.

``DsArray`` ops are written as pure per-block math and let SPMD partitioning
choose the collective schedule.  For the §Perf hillclimb we also provide
hand-scheduled ``shard_map`` versions with explicit collectives so the HLO
contains exactly the collective pattern we intend:

* ``summa_matmul``     — SUMMA (gather form): all-gather the A panel along the
  ``model`` axis and the B panel along the ``data`` axis, local GEMM.
  Communication per device: n*k/dn + k*m/dm elements (see
  ``core.costmodel.tpu_summa_bytes``).
* ``cannon_matmul``    — Cannon's algorithm: one-shot skew ppermute, then d-1
  neighbour ``ppermute`` steps of both operands, each overlapping the local
  GEMM.  Same total bytes as SUMMA but all steady-state traffic is
  nearest-neighbour over ICI — the beyond-paper schedule evaluated in §Perf.
* ``transpose_pp``     — local block transpose + ONE mirrored ``ppermute``
  across the square (data × model) mesh: the minimal-communication transpose
  (each shard moves exactly once).  The paper's N-task transpose maps to this.

All bodies take/return *mesh-local* stacked block tensors inside
``shard_map``; wrappers handle DsArray packing/padding.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.blocking import BlockGrid, round_up
from repro.core.dsarray import DsArray
from repro.core import structural


def _shmap(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def _local_gemm(a: jnp.ndarray, b: jnp.ndarray,
                gemm: Union[str, Callable, None] = None) -> jnp.ndarray:
    """Local blocked GEMM on stacked tiles: (gi,gk,bn,bk) x (gk,gj,bk,bm).

    Dispatches through ``kernels.matmul.local_matmul`` — on TPU the whole
    shard contracts in ONE fused Pallas launch with the (grid-k x block-k)
    reduction accumulating in a VMEM fp32 tile.  This replaces the old
    per-grid-k Python loop of vmapped 2-D kernels, which launched O(gk)
    kernels and round-tripped the full C partial through HBM at every step.
    ``gemm`` selects a backend ("pallas" / "interpret" / "einsum", None =
    auto) or is a callable taking the two stacked tensors.
    """
    from repro.kernels.matmul.ops import local_matmul
    if callable(gemm):
        return gemm(a, b)
    return local_matmul(a, b, out_dtype=a.dtype, backend=gemm)


def _prep_matmul(a: DsArray, b: DsArray, mesh: Mesh, axes):
    if a.shape[1] != b.shape[0] or a.block_shape[1] != b.block_shape[0]:
        raise ValueError("distributed matmul requires matching inner grid/block dims")
    # shard bodies read raw blocks; the padded contraction is exact only
    # with zero pads (enforced once here, not per schedule step)
    a = a.ensure_zero_pad().distribute(mesh, axes)
    b = b.ensure_zero_pad().distribute(mesh, axes)
    dn, dm = mesh.shape[axes[0]], mesh.shape[axes[1]]
    gk = round_up(max(a.stacked_grid[1], b.stacked_grid[0]), dn * dm)
    a = a._pad_grid_to((a.stacked_grid[0], gk))
    b = b._pad_grid_to((gk, b.stacked_grid[1]))
    return a, b


def summa_matmul(a: DsArray, b: DsArray, mesh: Mesh,
                 axes: Tuple[str, str] = ("data", "model"),
                 gemm: Union[str, Callable, None] = None) -> DsArray:
    """C = A @ B with an explicit SUMMA (gather-form) schedule."""
    a, b = _prep_matmul(a, b, mesh, axes)

    def body(ab, bb):
        a_full = jax.lax.all_gather(ab, axes[1], axis=1, tiled=True)  # (gi/dn, gk, ., .)
        b_full = jax.lax.all_gather(bb, axes[0], axis=0, tiled=True)  # (gk, gj/dm, ., .)
        return _local_gemm(a_full, b_full, gemm)

    spec = P(axes[0], axes[1], None, None)
    out_blocks = _shmap(body, mesh, (spec, spec), spec)(a.blocks, b.blocks)
    grid = BlockGrid((a.shape[0], b.shape[1]),
                     (a.block_shape[0], b.block_shape[1]))
    return DsArray(out_blocks, grid)


def cannon_matmul(a: DsArray, b: DsArray, mesh: Mesh,
                  axes: Tuple[str, str] = ("data", "model"),
                  gemm: Union[str, Callable, None] = None) -> DsArray:
    """Cannon's algorithm on a square (d × d) mesh slice.

    Steady state: per step, every device ppermutes its A panel one hop left
    and its B panel one hop up while computing the local GEMM — compute/comm
    overlap with only nearest-neighbour ICI traffic.
    """
    dn, dm = mesh.shape[axes[0]], mesh.shape[axes[1]]
    if dn != dm:
        raise ValueError("cannon_matmul requires a square mesh slice")
    d = dn
    a, b = _prep_matmul(a, b, mesh, axes)
    joint = (axes[0], axes[1])

    left = [(c, (c - 1) % d) for c in range(d)]   # along axes[1]
    up = [(r, (r - 1) % d) for r in range(d)]     # along axes[0]
    skew_a = [(r * d + c, r * d + ((c - r) % d)) for r in range(d) for c in range(d)]
    skew_b = [(r * d + c, ((r - c) % d) * d + c) for r in range(d) for c in range(d)]

    def body(ab, bb):
        ab = jax.lax.ppermute(ab, joint, skew_a)
        bb = jax.lax.ppermute(bb, joint, skew_b)
        acc = _local_gemm(ab, bb, gemm)
        for _ in range(d - 1):
            ab = jax.lax.ppermute(ab, axes[1], left)
            bb = jax.lax.ppermute(bb, axes[0], up)
            acc = acc + _local_gemm(ab, bb, gemm)
        return acc

    spec = P(axes[0], axes[1], None, None)
    out_blocks = _shmap(body, mesh, (spec, spec), spec)(a.blocks, b.blocks)
    grid = BlockGrid((a.shape[0], b.shape[1]),
                     (a.block_shape[0], b.block_shape[1]))
    return DsArray(out_blocks, grid)


def transpose_pp(a: DsArray, mesh: Mesh,
                 axes: Tuple[str, str] = ("data", "model")) -> DsArray:
    """Transpose = local block transpose + ONE mirrored ppermute (square mesh).

    Device (r, c) locally transposes its shard and sends it to device (c, r);
    every byte crosses the mesh exactly once — strictly cheaper than the
    all-to-all XLA emits for the einsum formulation (measured in §Perf).
    """
    dn, dm = mesh.shape[axes[0]], mesh.shape[axes[1]]
    if dn != dm:
        raise ValueError("transpose_pp requires a square mesh slice; use the "
                         "default DsArray.transpose() under pjit otherwise")
    d = dn
    a = a.distribute(mesh, axes)
    gn, gm = a.stacked_grid
    a = a._pad_grid_to((round_up(gn, d), round_up(gm, d)))
    mirror = [(r * d + c, c * d + r) for r in range(d) for c in range(d)]

    def body(x):  # (gn/d, gm/d, bn, bm) local
        xt = jnp.swapaxes(jnp.swapaxes(x, 0, 1), 2, 3)
        return jax.lax.ppermute(xt, (axes[0], axes[1]), mirror)

    spec = P(axes[0], axes[1], None, None)
    out_blocks = _shmap(body, mesh, (spec,), spec)(a.blocks)
    # pure permutation: the pad region maps onto the transposed pad region,
    # so the operand's pad state (and constant) carries over
    return DsArray(out_blocks, a.grid.transpose(), a.pad_state)


def colsum_psum(a: DsArray, mesh: Mesh,
                axes: Tuple[str, str] = ("data", "model")) -> DsArray:
    """Paper Fig. 5 column-of-blocks summation with an explicit psum over the
    `data` axis (one partial-sum 'task' per device, one reduction)."""
    a = a.distribute(mesh, axes)

    def body(x):  # (gn/dn, gm/dm, bn, bm)
        partial = x.sum(axis=(0, 2))          # (gm/dm, bm)
        total = jax.lax.psum(partial, axes[0])
        return total[None, :, None, :]        # (1, gm/dm, 1, bm)

    spec = P(axes[0], axes[1], None, None)
    out_spec = P(None, axes[1], None, None)
    out_blocks = _shmap(body, mesh, (spec,), out_spec)(a.ensure_zero_pad().blocks)
    grid = BlockGrid((1, a.shape[1]), (1, a.block_shape[1]))
    return DsArray(out_blocks, grid)


# ---------------------------------------------------------------------------
# Sharding-preserving structural ops.
#
# The block-native structural ops in ``core.structural`` are pure jnp, so
# under jit SPMD keeps blocks in place automatically, and eagerly they re-put
# the result on the operand's mesh.  The wrappers below are the explicit
# distributed entry points: they first place the operand on ``mesh`` (padding
# the grid to mesh multiples), run the block-native op, and guarantee the
# result carries a ``NamedSharding`` over the same axes — the SPMD analogue
# of the paper's "slicing returns a ds-array with the same worker placement".
# ---------------------------------------------------------------------------


def _redistribute(out: DsArray, mesh: Mesh, axes) -> DsArray:
    from jax.sharding import NamedSharding
    spec = P(axes[0], axes[1], None, None)
    dn = mesh.shape[axes[0]] if axes[0] else 1
    dm = mesh.shape[axes[1]] if axes[1] else 1
    gn, gm = out.stacked_grid
    padded = out._pad_grid_to((round_up(gn, dn), round_up(gm, dm)))
    blocks = jax.device_put(padded.blocks, NamedSharding(mesh, spec))
    return DsArray(blocks, out.grid, padded.pad_state)


def slice_sharded(a: DsArray, key, mesh: Mesh,
                  axes: Tuple[str, str] = ("data", "model")) -> DsArray:
    """``A[key]`` on a mesh: block-native selection, result resharded."""
    a = a.distribute(mesh, axes)
    return _redistribute(structural.getitem(a, key), mesh, axes)


def rechunk_sharded(a: DsArray, block_shape: Tuple[int, int], mesh: Mesh,
                    axes: Tuple[str, str] = ("data", "model")) -> DsArray:
    """Re-block on a mesh: grid-local regroup, result resharded."""
    a = a.distribute(mesh, axes)
    return _redistribute(structural.rechunk(a, block_shape), mesh, axes)


def concat_rows_sharded(arrays, mesh: Mesh,
                        axes: Tuple[str, str] = ("data", "model")) -> DsArray:
    """Vertical concat on a mesh: grid stack, result resharded."""
    arrays = [a.distribute(mesh, axes) for a in arrays]
    return _redistribute(structural.concat_rows(arrays), mesh, axes)
