"""Blockwise (flash) attention Pallas TPU kernel with GQA + sliding window.

The compute hot-spot of every attention arch at long context.  VMEM tiling:
one (block_q, head_dim) query tile and one (block_k, head_dim) key/value
tile resident per grid step; online-softmax running stats live in VMEM
scratch shaped (block_q, 128) (lane-replicated, the standard TPU layout for
per-row scalars).  Grid is (batch*q_heads, n_q_blocks, n_kv_blocks) with the
kv dimension 'arbitrary' (sequential accumulation); causal/windowed tiles
that are fully out-of-band are skipped with pl.when, so a w-token sliding
window does O(S*w) work, not O(S^2).

GQA is handled in the BlockSpec index maps: the kv block for flat head
index bh = b*H + h is (b*K + h // (H//K)) -- no materialized repeat.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr,
                  *, scale: float, block_q: int, block_k: int,
                  n_kv_blocks: int, causal: bool, window: Optional[int]):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # Block-level band check (static shapes, dynamic program ids).
    in_band = jnp.bool_(True)
    if causal:
        in_band &= k_start <= q_start + block_q - 1
    if window is not None:
        in_band &= k_start + block_k - 1 > q_start - window

    @pl.when(in_band)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                      # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                     # [bq, bk]
        corr = jnp.exp(m_prev - m_new)             # [bq, 1]
        l_new = l_scr[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0, ...] = (acc_scr[...] /
                         jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,                  # [B, H, S, D]
    k: jax.Array,                  # [B, K, S, D]
    v: jax.Array,                  # [B, K, S, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, s, d = q.shape
    kv_heads = k.shape[1]
    assert h % kv_heads == 0, "GQA requires H % K == 0"
    group = h // kv_heads
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0
    n_q, n_kv = s // block_q, s // block_k
    scale = 1.0 / (d ** 0.5)

    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * kv_heads, s, d)
    vf = v.reshape(b * kv_heads, s, d)

    def q_index(bh, qi, ki):
        return (bh, qi, 0)

    def kv_index(bh, qi, ki):
        return (bh // h * kv_heads + (bh % h) // group, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_kv_blocks=n_kv, causal=causal, window=window)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_index),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running sum
            pltpu.VMEM((block_q, d), jnp.float32),        # output accum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)
