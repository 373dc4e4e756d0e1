"""Fused K-means assignment kernel (paper §5.5's per-Subset task, fused).

One grid step processes a (block_n × D) tile of samples against the full
(K × D) center table resident in VMEM:

    distances (MXU: x·cᵀ) → argmin → one-hot → partial sums (MXU: onehotᵀ·x)

all without re-touching HBM — this is the entire per-iteration inner loop of
K-means as a single kernel.  The per-cluster sums/counts OUTPUT BLOCKS are
revisited by every grid step (index_map → block 0) with the K reduction
running over the sequential grid dimension, which is the TPU analogue of the
paper's partial-sum tasks + reduction tree (Fig. 3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kmeans_kernel(x_ref, c_ref, labels_ref, sums_ref, counts_ref, *,
                   n: int, block_n: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = x_ref[...]                      # (block_n, D)
    c = c_ref[...]                      # (K, D)
    dots = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    x_sq = jnp.sum(x.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    c_sq = jnp.sum(c.astype(jnp.float32) ** 2, axis=1)
    dist = x_sq - 2.0 * dots + c_sq[None, :]          # (block_n, K)

    rows = i * block_n + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 0)
    valid = rows < n
    labels = jnp.argmin(dist, axis=1).astype(jnp.int32)  # (block_n,)
    labels_ref[...] = jnp.where(valid[:, :1][:, 0], labels, -1)[:, None]

    k_iota = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    onehot = ((labels[:, None] == k_iota) & valid).astype(jnp.float32)
    sums_ref[...] += jax.lax.dot_general(
        onehot, x.astype(jnp.float32), (((0,), (0,)), ((), ())))
    counts_ref[...] += jnp.sum(onehot, axis=0)[:, None] * jnp.ones(
        (1, counts_ref.shape[1]), jnp.float32)


def kmeans_assign_padded(
    x: jnp.ndarray,        # (N_pad, D) pad rows beyond n
    centers: jnp.ndarray,  # (K_pad, D) pad centers pushed far away by ops
    *,
    n: int,
    block_n: int = 512,
    interpret: bool = False,
):
    n_pad, d = x.shape
    k_pad = centers.shape[0]
    assert n_pad % block_n == 0
    grid = (n_pad // block_n,)
    kernel = functools.partial(_kmeans_kernel, n=n, block_n=block_n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k_pad, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((k_pad, d), lambda i: (0, 0)),
            pl.BlockSpec((k_pad, 128), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((k_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((k_pad, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x, centers)
