"""Foundation layers: norms, RoPE, GQA attention (full / sliding-window /
chunked-online-softmax / decode-with-cache), MLPs.

Pure functional style: ``init_*`` builds a params dict, ``*_apply`` consumes
it.  Everything is einsum-based so GSPMD can partition freely; the chunked
attention path keeps peak memory at O(S * chunk) for long sequences and is
mathematically identical to the Pallas flash_attention kernel (same online
softmax; the kernel is the TPU-optimized form, this is the partitioner- and
CPU-friendly form).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .sharding import logical_constraint
from .. import scopes
from ..configs.registry import ModelConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = float(1.0 / np.sqrt(d_in))  # python float: no dtype promotion
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return jax.random.normal(key, (vocab, d), dtype=jnp.float32).astype(
        dtype) * 0.02


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, d: int) -> dict:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = x32.mean(-1, keepdims=True)
        var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + 1e-6)
        y = y * p["scale"] + p["bias"]
    else:
        ms = (x32 ** 2).mean(-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(ms + 1e-6) * p["scale"]
    return y.astype(dtype)


def rms_head_norm(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Per-head RMS norm over head_dim (Qwen3 qk-norm)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 ** 2).mean(-1, keepdims=True) + 1e-6)
    return (y * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, Dh]; positions: [..., S] (broadcastable)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # [Dh/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, Dh/2]
    cos = jnp.cos(angles)[..., None, :]                 # [..., S, 1, Dh/2]
    sin = jnp.sin(angles)[..., None, :]
    # Pin the head-free rotation tables replicated over the TP axis: without
    # this, GSPMD propagates conflicting (q:16-way, kv:8x2-way) shardings
    # into the broadcast and inserts involuntary full rematerializations.
    cos = logical_constraint(cos, "batch", "act_seq", None, None)
    sin = logical_constraint(sin, "batch", "act_seq", None, None)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnParamsSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int


def init_attention(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, h, k, dh = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                   cfg.resolved_head_dim)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * dh, dtype),
        "wk": dense_init(ks[1], d, k * dh, dtype),
        "wv": dense_init(ks[2], d, k * dh, dtype),
        "wo": dense_init(ks[3], h * dh, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), jnp.float32)
        p["k_norm"] = jnp.ones((dh,), jnp.float32)
    return p


@scopes.scoped(scopes.ATTENTION_PROJ)
def _project_qkv(cfg: ModelConfig, p: dict, x: jax.Array,
                 positions: jax.Array, rope: bool = True):
    b, s, _ = x.shape
    h, k, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, h, dh)
    kk = (x @ p["wk"].astype(x.dtype)).reshape(b, s, k, dh)
    v = (x @ p["wv"].astype(x.dtype)).reshape(b, s, k, dh)
    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"])
        kk = rms_head_norm(kk, p["k_norm"])
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        kk = apply_rope(kk, positions, cfg.rope_theta)
    q = logical_constraint(q, "batch", "act_seq", "heads", "head_dim")
    # kv heads (2-8) never divide the 16-way TP axis; sharding them forces
    # GSPMD to regather q-sized tensors every layer.  Replicating kv over
    # "model" keeps attention score/context einsums fully local per q-head
    # shard at the cost of one small K*dh all-gather after the projection.
    kk = logical_constraint(kk, "batch", "act_seq", None, None)
    v = logical_constraint(v, "batch", "act_seq", None, None)
    return q, kk, v


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return x
    b, s, k, dh = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, s, k, n_rep, dh)).reshape(b, s, k * n_rep, dh)


def _band_mask(sq: int, skv: int, q_offset, window: Optional[int],
               causal: bool) -> jax.Array:
    """[sq, skv] bool mask. q position = q_offset + i, kv position = j."""
    qi = q_offset + jnp.arange(sq)[:, None]
    kj = jnp.arange(skv)[None, :]
    m = jnp.ones((sq, skv), bool)
    if causal:
        m &= kj <= qi
    if window is not None:
        m &= kj > qi - window
    return m


def mha_einsum(q, k, v, mask) -> jax.Array:
    """Reference attention: q [B,Sq,H,Dh], k/v [B,Skv,H,Dh], mask [Sq,Skv]."""
    dh = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    scores = jnp.where(mask[None, None], scores.astype(jnp.float32), NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def mha_chunked(q, k, v, *, q_offset, window: Optional[int], causal: bool,
                use_window=True, q_chunk: int = 1024,
                kv_chunk: int = 1024) -> jax.Array:
    """Online-softmax chunked attention: O(Sq*chunk) memory, flash-equivalent.

    Sliding-window chunks that fall fully outside the band are not skipped
    statically here (XLA-friendly uniform loop) but contribute zero after
    masking; the Pallas kernel does skip them.  For *very* long windowed
    prefills use kernel path on TPU.
    """
    b, sq, h, dh = q.shape
    skv = k.shape[1]
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    n_q, n_kv = sq // q_chunk, skv // kv_chunk
    scale = 1.0 / np.sqrt(dh)

    q_r = q.reshape(b, n_q, q_chunk, h, dh)

    def per_qchunk(qi, qc):
        # qc: [b, q_chunk, h, dh]
        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        acc0 = jnp.zeros((b, q_chunk, h, dh), jnp.float32)

        def per_kvchunk(carry, kj):
            m_prev, l_prev, acc = carry
            kc = jax.lax.dynamic_slice_in_dim(k, kj * kv_chunk, kv_chunk, 1)
            vc = jax.lax.dynamic_slice_in_dim(v, kj * kv_chunk, kv_chunk, 1)
            s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc).astype(
                jnp.float32) * scale
            qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)[:, None]
            kpos = kj * kv_chunk + jnp.arange(kv_chunk)[None, :]
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= kpos <= qpos
            if window is not None:
                band = kpos > qpos - window
                mask &= jnp.logical_or(
                    jnp.logical_not(jnp.asarray(use_window)), band)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + p.sum(-1)
            acc = acc * corr.transpose(0, 2, 1)[..., None]
            acc = acc + jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype),
                                   vc).astype(jnp.float32)
            return (m_new, l_new, acc), None

        (m, l, acc), _ = jax.lax.scan(
            per_kvchunk, (m0, l0, acc0), jnp.arange(n_kv))
        out = acc / jnp.maximum(l.transpose(0, 2, 1)[..., None], 1e-30)
        return out.astype(q.dtype)

    outs = jax.lax.map(lambda args: per_qchunk(*args),
                       (jnp.arange(n_q), q_r.swapaxes(0, 1)))
    return outs.swapaxes(0, 1).reshape(b, sq, h, dh)


def attention_apply(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    *,
    positions: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    use_window=True,
    chunked_threshold: int = 2048,
    return_kv: bool = False,
):
    """Self-attention over a full sequence (training / prefill).

    ``window`` is the static band size; ``use_window`` may be a traced bool
    (scan-over-layers with per-layer full-attention overrides) -- when falsy
    the band constraint is disabled.  With ``return_kv`` also returns the
    (pre-GQA-repeat) keys/values arranged as a ring-consistent decode cache.
    """
    b, s, d = x.shape
    h, kv = cfg.n_heads, cfg.n_kv_heads
    q, k, v = _project_qkv(cfg, p, x, positions)
    with jax.named_scope(scopes.ATTENTION_CORE):
        kr = _repeat_kv(k, h // kv)
        vr = _repeat_kv(v, h // kv)
        eff_window = window if (window is not None) else None
        if s > chunked_threshold:
            out = mha_chunked(q, kr, vr, q_offset=0, window=eff_window,
                              use_window=use_window, causal=causal)
        else:
            mask = _band_mask(s, s, 0, eff_window, causal)
            if eff_window is not None:
                full = _band_mask(s, s, 0, None, causal)
                mask = jnp.where(jnp.asarray(use_window), mask, full)
            out = mha_einsum(q, kr, vr, mask)
    with jax.named_scope(scopes.ATTENTION_PROJ):
        out = out.reshape(b, s, h * cfg.resolved_head_dim)
        out = out @ p["wo"].astype(out.dtype)
        out = logical_constraint(out, "batch", "act_seq", "model_dim")
    if not return_kv:
        return out
    return out, (k, v)


@scopes.scoped(scopes.ATTENTION_KV_CACHE)
def assemble_kv_cache(k: jax.Array, v: jax.Array, window: Optional[int],
                      cache_len: int) -> Tuple[jax.Array, jax.Array]:
    """Place prefill keys/values [B, S, K, Dh] into a head-major decode
    cache [B, K, S_phys, Dh], S_phys = min(cache_len, window or cache_len),
    ring-aligned so position p lives at slot p % S_phys (matching
    attention_decode)."""
    s = k.shape[1]
    phys = cache_len if window is None else min(cache_len, window)

    def place(x):
        x = x.swapaxes(1, 2)
        if s >= phys:
            xw = x[:, :, s - phys:]
            shift = s % phys
            return jnp.roll(xw, shift, axis=2) if shift else xw
        pad = [(0, 0)] * x.ndim
        pad[2] = (0, phys - s)
        return jnp.pad(x, pad)

    return place(k), place(v)


def attention_decode(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,                   # [B, 1, d]
    cache_k: jax.Array,             # [B, K, S_phys, Dh], or [L, B, K, ...]
    cache_v: jax.Array,
    pos: jax.Array,                 # scalar: index of the new token
    *,
    window: Optional[int] = None,
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against a (ring-buffered, if windowed) KV cache.

    The cache is head-major: each kv head's [S_phys, Dh] slots are one
    block, the operand order of the grouped-query dots on a TPU.  With
    ``layer`` the caches are the whole layer stack, read and written at
    ``layer``.  Attention reads the slots as they were before this step
    and takes the new token's key and value from the projection; the new
    row is written last, in place.  A read after the write would keep XLA
    from fusing the layer's slice into the dots, which would then copy it.
    Returns the output and the updated caches."""
    b = x.shape[0]
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    s_phys = cache_k.shape[-2]
    positions = jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32)
    q, k_new, v_new = _project_qkv(cfg, p, x, positions=positions)
    # Decode shards the KV cache over head_dim ("kv_feature" -> model); q
    # must match, or GSPMD all-gathers the entire cache per layer.  Scores
    # become dh-partial dots psum'd over "model" -- tiny [B,H,S] traffic vs
    # gigabytes of cache movement.
    q = logical_constraint(q, "batch", "act_seq", None, "kv_feature")
    k_new = logical_constraint(k_new, "batch", "act_seq", None, "kv_feature")
    v_new = logical_constraint(v_new, "batch", "act_seq", None, "kv_feature")
    # RoPE-at-write: keys stored already rotated at their absolute position,
    # so ring-buffer slot order is irrelevant (softmax is permutation
    # invariant over kv slots).
    slot = pos if window is None else pos % s_phys
    k_l, v_l = cache_k, cache_v
    if layer is not None:
        with jax.named_scope(scopes.ATTENTION_KV_CACHE):
            k_l = jax.lax.dynamic_index_in_dim(cache_k, layer, keepdims=False)
            v_l = jax.lax.dynamic_index_in_dim(cache_v, layer, keepdims=False)
    # Grouped-query einsum against the raw cache: materializing the GQA
    # repeat would force an all-gather of the dh-sharded cache.
    g = h // kv
    with jax.named_scope(scopes.ATTENTION_CORE):
        q5 = q.reshape(b, 1, kv, g, dh)
        scores = jnp.einsum("bqkgd,bksd->bqkgs", q5, k_l).astype(
            jnp.float32) / np.sqrt(dh)
        own = jnp.einsum("bqkgd,bqkd->bqkg", q5, k_new).astype(
            jnp.float32)[..., None] / np.sqrt(dh)
        # Valid slots: the min(pos, s_phys) most recent earlier positions,
        # less the slot this token takes (the ring's oldest, now out of the
        # window).  For the windowed ring buffer (s_phys == window) every
        # written slot is in-window by construction.
        idx = jnp.arange(s_phys)
        valid = (idx < jnp.minimum(pos, s_phys)) & (idx != slot)
        scores = jnp.where(valid[None, None, None, None, :], scores,
                           NEG_INF)
        probs = jax.nn.softmax(jnp.concatenate([scores, own], -1),
                               axis=-1).astype(x.dtype)
        out = jnp.einsum("bqkgs,bksd->bqkgd", probs[..., :-1], v_l)
        out = (out.astype(jnp.float32) + probs[..., -1:].astype(jnp.float32)
               * v_new[:, :, :, None].astype(jnp.float32)).astype(x.dtype)
    with jax.named_scope(scopes.ATTENTION_PROJ):
        out = out.reshape(b, 1, h * dh) @ p["wo"].astype(x.dtype)
    with jax.named_scope(scopes.ATTENTION_KV_CACHE):
        # the new rows [B, K, 1, Dh], at ((layer,) 0, 0, slot, 0)
        k_row, v_row = k_new.swapaxes(1, 2), v_new.swapaxes(1, 2)
        at = (0, 0, slot, 0)
        if layer is not None:
            k_row, v_row, at = k_row[None], v_row[None], (layer,) + at
        cache_k = jax.lax.dynamic_update_slice(cache_k, k_row, at)
        cache_v = jax.lax.dynamic_update_slice(cache_v, v_row, at)
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None,
             dtype=jnp.float32) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "silu":
        return {
            "w_gate": dense_init(ks[0], d, f, dtype),
            "w_up": dense_init(ks[1], d, f, dtype),
            "w_down": dense_init(ks[2], f, d, dtype),
        }
    return {
        "w_up": dense_init(ks[0], d, f, dtype),
        "b_up": jnp.zeros((f,), jnp.float32),
        "w_down": dense_init(ks[1], f, d, dtype),
        "b_down": jnp.zeros((d,), jnp.float32),
    }


def mlp_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.act == "silu":
        h = jax.nn.silu(x @ p["w_gate"].astype(x.dtype)) * (
            x @ p["w_up"].astype(x.dtype))
        h = logical_constraint(h, "batch", "act_seq", "ff")
        out = h @ p["w_down"].astype(x.dtype)
    else:
        h = jax.nn.gelu(x @ p["w_up"].astype(x.dtype)
                        + p["b_up"].astype(x.dtype))
        h = logical_constraint(h, "batch", "act_seq", "ff")
        out = h @ p["w_down"].astype(x.dtype) + p["b_down"].astype(x.dtype)
    return logical_constraint(out, "batch", "act_seq", "model_dim")
