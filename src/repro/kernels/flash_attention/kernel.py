"""Fused attention kernel (FlashAttention-style online softmax) for TPU.

Covers the attention variants of the assigned architecture pool in ONE body:

* causal / bidirectional          (decoder LMs vs the seamless encoder)
* GQA                             (every LM arch: kv_heads <= q_heads)
* sliding window                  (mistral/llava, mixtral, gemma2 local layers)
* logit soft-capping              (gemma2, grok-1)

TPU adaptation (vs the CUDA flash-attention): the online-softmax state
(m, l, acc) lives in VMEM scratch across the sequential KV grid dimension;
each (q-block × kv-block) score tile is one MXU matmul.  Block shapes are
(block_q × head_dim) and (block_k × head_dim) with head_dim padded to 128
lanes by ops.py.  Grid = (batch, q_heads, q_blocks, kv_blocks) with the KV
dimension innermost/sequential ("arbitrary") so the scratch carry is legal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 n_kv: int, block_q: int, block_k: int, causal: bool,
                 window: int, softcap: float, sm_scale: float,
                 q_offset: int, kv_len: int):
    """One (q-block, kv-block) step of online-softmax attention.

    q_ref: (block_q, d); k_ref/v_ref: (block_k, d); o_ref: (block_q, d)
    scratch: m/l (block_q, 128) fp32 (lane-replicated), acc (block_q, d) fp32.
    ``q_offset`` shifts absolute q positions (decode: q_len << kv_len).
    """
    kv_idx = pl.program_id(3)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale   # (block_q, block_k)

    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    # absolute positions of this tile
    q_pos = (pl.program_id(2) * block_q + q_offset
             + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
    k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < kv_len            # hide KV padding
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]                                  # (block_q, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)              # (block_q, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (all NEG_INF): keep exp at 0
    p = jnp.exp(s - m_new)                                  # (block_q, block_k)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)                          # (block_q, 1)
    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kv_idx == n_kv - 1)
    def _flush():
        l = l_ref[:, :1]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_attention_padded(
    q: jnp.ndarray,   # (B, Hq, Tq, D)
    k: jnp.ndarray,   # (B, Hkv, Tk, D)
    v: jnp.ndarray,   # (B, Hkv, Tk, D)
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    sm_scale: float = 1.0,
    q_offset: int = 0,
    kv_len: int = 0,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Attention over block-padded inputs. All of Tq % block_q, Tk % block_k,
    Hq % Hkv must be 0 (ops.py guarantees this). ``kv_len`` is the logical
    (unpadded) key count; 0 means Tk."""
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    assert tq % block_q == 0 and tk % block_k == 0 and hq % hkv == 0
    group = hq // hkv
    grid = (b, hq, tq // block_q, tk // block_k)

    kernel = functools.partial(
        _attn_kernel, n_kv=grid[3], block_q=block_q, block_k=block_k,
        causal=causal, window=window, softcap=softcap, sm_scale=sm_scale,
        q_offset=q_offset, kv_len=kv_len or tk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, h, iq, jk: (bb, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, h, iq, jk, g=group: (bb, h // g, jk, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, h, iq, jk, g=group: (bb, h // g, jk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, h, iq, jk: (bb, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
