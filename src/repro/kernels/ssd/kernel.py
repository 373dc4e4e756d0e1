"""Mamba-2 SSD (state-space duality) chunk kernel.

The SSD insight (Dao & Gu, arXiv:2405.21060) is the paper's 2-D blocking idea
applied to the (sequence × state) plane: cut the sequence into chunks so the
recurrence

    h_t = exp(A·dt_t) h_{t-1} + dt_t · B_t x_tᵀ ,   y_t = C_t h_t

becomes, per chunk, three MXU matmuls (the "dual" quadratic form) plus a tiny
inter-chunk scan:

    y_intra = (C Bᵀ ⊙ decay-mask) @ (dt ⊙ x)          (L×L)·(L×P)
    state   = (B ⊙ dt ⊙ decay-to-end)ᵀ @ x            (S×L)·(L×P)
    y_inter = (C ⊙ decay-from-start) @ h_prev          (L×S)·(S×P)

This kernel computes the chunk-local quantities (everything except the
h_prev recurrence, which ops.py runs as an associative scan over chunk
states).  Grid = (batch·heads, n_chunks); per step the (L×P) x-tile, (L×S)
B/C tiles and the (L×L) decay tile live in VMEM.

All decays are exp of non-positive numbers (A<0, dt>0) — no overflow.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                      y_ref, state_ref, cdec_ref, chunk_dec_ref, *,
                      chunk: int):
    x = x_ref[0]            # (L, P)
    dt = dt_ref[0]          # (L, 1)
    a = a_ref[0, 0]         # scalar, negative, for this head
    b = b_ref[0]            # (L, S)
    c = c_ref[0]            # (L, S)

    lda = a * dt                                        # (L, 1) log-decays
    ell = jnp.cumsum(lda, axis=0)                       # (L, 1) inclusive
    # pairwise decay  exp(ell_t - ell_s)  masked to s <= t
    diff = ell - ell[:, 0][None, :]                     # (L, L): [t, s]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
    mask = t_idx >= s_idx
    # clamp masked (s>t) region before exp: overflow there would be inf
    gate = jnp.where(mask, jnp.exp(jnp.where(mask, diff, 0.0)), 0.0)

    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    w = scores * gate                                   # (L, L)
    xdt = x * dt                                        # (L, P)
    y_ref[0] = jax.lax.dot_general(
        w.astype(x.dtype), xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)

    # chunk state: sum_s exp(ell_L - ell_s) dt_s B_s x_sᵀ  -> (S, P)
    w_end = jnp.exp(ell[chunk - 1, 0] - ell)            # (L, 1)
    b_scaled = b * (w_end * dt)                         # (L, S)
    state_ref[0, 0] = jax.lax.dot_general(
        b_scaled, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(state_ref.dtype)

    # decayed C for the inter-chunk pass: C_t ⊙ exp(ell_t)
    cdec_ref[0] = (c * jnp.exp(ell)).astype(cdec_ref.dtype)
    # total chunk decay exp(ell_L) (lane-replicated scalar)
    chunk_dec_ref[0, 0] = (jnp.exp(ell[chunk - 1, 0])
                           * jnp.ones_like(chunk_dec_ref[0, 0]))


def ssd_chunk_padded(
    x: jnp.ndarray,    # (BH, T, P)
    dt: jnp.ndarray,   # (BH, T, 1)
    a: jnp.ndarray,    # (BH, 1)     negative per-head decay rates
    b: jnp.ndarray,    # (BH, T, S)
    c: jnp.ndarray,    # (BH, T, S)
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    """Chunk-local SSD quantities; T must divide by ``chunk`` (ops pads).

    Returns (y_intra (BH,T,P), states (BH,NC,S,P), c_decayed (BH,T,S),
    chunk_decay (BH,NC,1,128))."""
    bh, t, p = x.shape
    s = b.shape[-1]
    assert t % chunk == 0
    nc = t // chunk
    grid = (bh, nc)
    kernel = functools.partial(_ssd_chunk_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, chunk, s), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, s), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, s, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, chunk, s), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 1, 128), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, p), x.dtype),
            jax.ShapeDtypeStruct((bh, nc, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, t, s), x.dtype),
            jax.ShapeDtypeStruct((bh, nc, 1, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(x, dt, a, b, c)
