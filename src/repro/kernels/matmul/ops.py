"""Public wrappers for the matmul kernels: padding, dtype and backend policy.

``matmul(a, b)`` accepts arbitrary (m, k) x (k, n) shapes; inputs are padded
to MXU-aligned block multiples (pad contributes zeros to the K reduction, so
results are exact) and the output is sliced back.

``local_matmul(a, b)`` is the local GEMM under every ds-array ``@``, every
shmap schedule and the lazy plan's folded ``Aᵀ @ B``: it takes the stacked
block tensors directly.  ``gemm_backend`` sends it to the compiled Pallas
``stacked_matmul`` kernel on a TPU for f32/bf16 blocks whose dims meet the
Mosaic tiling (lanes a multiple of 128, sublanes of 8), and to a
stacked-block ``jnp.einsum`` everywhere else: off the TPU, for other dtypes
(Mosaic refuses f16 loads), for unaligned blocks, and for an operand whose
blocks lie on a mesh that XLA partitions (a Mosaic kernel cannot be
partitioned automatically; inside ``shard_map`` the operands are local, so
SUMMA and Cannon keep the kernel).  The backend can be forced with the
``REPRO_GEMM`` env var (``pallas`` / ``interpret`` / ``einsum``) or the
``backend=`` argument; tests use ``interpret`` to run the Pallas kernel in
the interpreter on the CPU.

A BCOO-blocked A (``core.sparse``) takes the sparse dispatch table instead:
one ``bcoo_dot_general`` over (grid-k, block-k), never densifying A.
"""

from __future__ import annotations

import functools
import os
import sys
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import sparse as jsparse
from jax.experimental.sparse import BCOO
from jax.sharding import AxisType

from repro.core.blocking import round_up
from repro.kernels.matmul.kernel import matmul_padded, stacked_matmul
from repro.obs import metrics as _metrics

# backend-dispatch decisions ("gemm.dispatch_*" in obs.snapshot()).  These
# count DECISIONS, not launches: inside a jitted plan body the dispatch
# (like its `_fire` hook) runs once at trace time — a span here would time
# tracing, not device work, so GEMM telemetry is counters only and per-op
# device time is the profiler's job (obs.profile).
_DISPATCHES = _metrics.CounterGroup(
    "gemm", ("dispatch_pallas", "dispatch_einsum", "dispatch_interpret",
             "dispatch_sparse"))


def _fire(site: str, **info) -> None:
    """Fault-injection hook: active only when ``repro.resilience.inject``
    is already imported (a chaos test armed it); clean runs pay one
    sys.modules lookup.  Fires at trace time, so an armed dispatch fault
    aborts the launch before any device work."""
    ri = sys.modules.get("repro.resilience.inject")
    if ri is not None:
        ri.maybe_fire(site, **info)


# Mosaic refuses the f16 kernel ("Invalid vector type for load")
_PALLAS_DTYPES = (jnp.float32, jnp.bfloat16)

# ---------------------------------------------------------------------------
# Sparse local GEMM: einsum-style dispatch table for BCOO-blocked operands.
#
# The sparse analogue of the backend policy below: a BCOO lhs contracts over
# BOTH the grid-k batch dim and the block-k sparse dim in ONE
# bcoo_dot_general (spec strings shown for reference — they are the einsum
# the dense fallback would run), so the stored entries are streamed exactly
# once and the sparse operand is never densified (no (bn, bk) dense
# intermediate appears in the jaxpr; asserted by tests/test_sparse.py).
# dot_general emits contracted-lhs-free dims first, hence the out_perm back
# to the stacked (gi, gj, bn, bm) layout.
# ---------------------------------------------------------------------------

_SPARSE_GEMM_SPECS = {
    # transpose_a: (einsum spec, ((contract), (batch)), out permutation)
    False: ("ikab,kjbc->ijac", (((1, 3), (0, 2)), ((), ())), (0, 2, 1, 3)),
    True:  ("kiba,kjbc->ijac", (((0, 2), (0, 2)), ((), ())), (0, 2, 1, 3)),
}


def _sparse_local_matmul(a: BCOO, b: jnp.ndarray, *, out_dtype,
                         transpose_a: bool) -> jnp.ndarray:
    """Blocked local GEMM with a BCOO-blocked A (see ``core.sparse``)."""
    if isinstance(b, BCOO):
        b = b.todense()         # sp @ sp densifies the right operand
    _, dimension_numbers, out_perm = _SPARSE_GEMM_SPECS[bool(transpose_a)]
    out = jsparse.bcoo_dot_general(a, b, dimension_numbers=dimension_numbers)
    return out.transpose(out_perm).astype(out_dtype)


def _mxu_aligned(bn: int, bk: int, bm: int, transpose_a: bool = False) -> bool:
    """True when every tile dim meets the Mosaic tiling without implicit
    padding: lane dims a multiple of 128, sublane dims of 8.  A's tile is
    ``(bn, bk)``, or ``(bk, bn)`` under ``transpose_a``; B's is ``(bk, bm)``."""
    if transpose_a:
        return bn % 128 == 0 and bk % 8 == 0 and bm % 128 == 0
    return bn % 8 == 0 and bk % 128 == 0 and bm % 128 == 0


def _auto_partitioned(x) -> bool:
    """True when ``x`` lives on a mesh whose axes XLA partitions by itself.
    The mesh is part of the value's type, so this holds for concrete and
    traced values alike.  Inside ``shard_map`` the axes are Manual: each
    device holds its own blocks, and the kernel runs on them."""
    mesh = jax.typeof(x).sharding.mesh
    return any(size > 1 and kind != AxisType.Manual
               for size, kind in zip(mesh.axis_sizes, mesh.axis_types))


def gemm_backend(bn: int, bk: int, bm: int, dtype,
                 backend: Optional[str] = None,
                 transpose_a: bool = False) -> str:
    """Resolve the local-GEMM backend: "pallas" | "interpret" | "einsum".

    Priority: explicit ``backend`` arg > ``REPRO_GEMM`` env var > auto.  Auto
    picks the compiled Pallas kernel exactly when Mosaic takes it: TPU
    backend, a dtype in ``_PALLAS_DTYPES``, MXU-aligned block dims.
    Everything else (CPU/GPU, f16, ints, ragged blocks) takes the einsum
    path.
    """
    forced = (backend or os.environ.get("REPRO_GEMM", "auto")).lower()
    if forced in ("pallas", "interpret", "einsum"):
        return forced
    if forced != "auto":
        raise ValueError(
            f"unknown GEMM backend {forced!r}: want pallas|interpret|einsum|auto")
    if jax.default_backend() != "tpu":
        return "einsum"
    if dtype not in [jnp.dtype(d) for d in _PALLAS_DTYPES]:
        return "einsum"
    if not _mxu_aligned(bn, bk, bm, transpose_a):
        return "einsum"
    return "pallas"


def local_matmul(a: jnp.ndarray, b: jnp.ndarray, *, out_dtype=None,
                 backend: Optional[str] = None,
                 transpose_a: bool = False) -> jnp.ndarray:
    """Blocked local GEMM on stacked tiles: (gi,gk,bn,bk) x (gk,gj,bk,bm).

    The single entry point for every local contraction in the repo —
    ``DsArray.__matmul__``, SUMMA and Cannon bodies, the lazy plan's folded
    ``Aᵀ @ B`` — so the backend policy lives in one place.

    ``transpose_a=True`` computes ``Aᵀ @ B`` with ``a`` still in its
    untransposed stacked layout ``(gk, gi, bk, bn)``: both backends fold the
    transpose into the contraction (block-index maps for Pallas, a relabeled
    einsum otherwise) instead of materializing the transposed tensor.
    """
    if transpose_a:
        gk, gi, bk, bn = a.shape
        gk2, gj, bk2, bm = b.shape
    else:
        gi, gk, bn, bk = a.shape
        gk2, gj, bk2, bm = b.shape
    if gk != gk2 or bk != bk2:
        raise ValueError(f"local_matmul inner mismatch {a.shape} x {b.shape}")
    out_dtype = out_dtype or jnp.promote_types(a.dtype, b.dtype)
    if isinstance(a, BCOO):
        _fire("gemm_dispatch", mode="sparse")
        _DISPATCHES.inc("dispatch_sparse")
        return _sparse_local_matmul(a, b, out_dtype=out_dtype,
                                    transpose_a=transpose_a)
    if isinstance(b, BCOO):
        b = b.todense()         # dense @ sp: right operand densifies
    if _auto_partitioned(a) or _auto_partitioned(b):
        # GSPMD partitions the einsum; a pallas_call it cannot partition
        backend = "einsum"
    mode = gemm_backend(bn, bk, bm, jnp.dtype(a.dtype), backend, transpose_a)
    _fire("gemm_dispatch", mode=mode)
    _DISPATCHES.inc(f"dispatch_{mode}")
    if mode == "einsum":
        preferred = None
        if jnp.issubdtype(a.dtype, jnp.floating):
            preferred = jnp.promote_types(a.dtype, jnp.float32)
        spec = "kiba,kjbc->ijac" if transpose_a else "ikab,kjbc->ijac"
        out = jnp.einsum(spec, a, b, preferred_element_type=preferred)
        return out.astype(out_dtype)
    return stacked_matmul(a, b, out_dtype=jnp.dtype(out_dtype),
                          interpret=(mode == "interpret"),
                          transpose_a=transpose_a)


def _pick_block(dim: int, target: int) -> int:
    """Largest multiple-of-128 block <= target that keeps padding small."""
    if dim <= 128:
        return 128
    return min(target, round_up(dim, 128))


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_m: int = 512,
    block_n: int = 512,
    block_k: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"matmul shape mismatch {a.shape} @ {b.shape}")
    bm = _pick_block(m, block_m)
    bn = _pick_block(n, block_n)
    bk = _pick_block(k, block_k)
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)
    a_p = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    out = matmul_padded(
        a_p, b_p, block_m=bm, block_n=bn, block_k=bk,
        out_dtype=out_dtype, interpret=interpret,
    )
    return out[:m, :n]
