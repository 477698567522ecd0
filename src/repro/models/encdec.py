"""Whisper-style encoder-decoder (audio frontend stubbed).

``input_specs`` feeds precomputed frame embeddings [B, encoder_len, d] --
the conv mel frontend is a stub per the assignment.  Encoder: non-causal
self-attention; decoder: causal self-attention + cross-attention with
learned positional embeddings, pre-LN, GELU MLPs, tied embedding head.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.registry import ModelConfig
from .dist import DistContext
from .layers import (
    attention_apply,
    attention_decode,
    embed_init,
    init_attention,
    init_mlp,
    init_norm,
    mha_einsum,
    mlp_apply,
    norm_apply,
    _repeat_kv,
)

__all__ = [
    "init_encdec", "encdec_loss", "encdec_forward",
    "encdec_init_cache", "encdec_decode_step",
]

_MAX_DECODE_POS = 8192  # learned positions table (structural superset)


def _init_cross_attention(key, cfg: ModelConfig) -> dict:
    # same projection structure; k/v read the encoder stream
    return init_attention(key, cfg)


def init_encdec(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    enc_blocks = []
    kb = jax.random.split(ks[0], cfg.n_encoder_layers)
    for i in range(cfg.n_encoder_layers):
        k1, k2 = jax.random.split(kb[i])
        enc_blocks.append({
            "norm1": init_norm(cfg, cfg.d_model),
            "attn": init_attention(k1, cfg),
            "norm2": init_norm(cfg, cfg.d_model),
            "mlp": init_mlp(k2, cfg),
        })
    dec_blocks = []
    kd = jax.random.split(ks[1], cfg.n_layers)
    for i in range(cfg.n_layers):
        k1, k2, k3 = jax.random.split(kd[i], 3)
        dec_blocks.append({
            "norm1": init_norm(cfg, cfg.d_model),
            "attn": init_attention(k1, cfg),
            "norm_x": init_norm(cfg, cfg.d_model),
            "xattn": _init_cross_attention(k2, cfg),
            "norm2": init_norm(cfg, cfg.d_model),
            "mlp": init_mlp(k3, cfg),
        })
    return {
        "embed": embed_init(ks[2], cfg.vocab, cfg.d_model, jnp.float32),
        "enc_pos": embed_init(ks[3], cfg.encoder_len, cfg.d_model,
                              jnp.float32),
        "dec_pos": embed_init(ks[4], _MAX_DECODE_POS, cfg.d_model,
                              jnp.float32),
        "enc_blocks": enc_blocks,
        "dec_blocks": dec_blocks,
        "enc_final": init_norm(cfg, cfg.d_model),
        "dec_final": init_norm(cfg, cfg.d_model),
    }


def _cross_attend(cfg: ModelConfig, p: dict, x, enc_k, enc_v):
    """x: [B, Sq, d]; enc_k/enc_v: [B, Se, K, Dh] (already projected)."""
    b, sq, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, sq, h, dh)
    k = _repeat_kv(enc_k, h // kv)
    v = _repeat_kv(enc_v, h // kv)
    mask = jnp.ones((sq, k.shape[1]), bool)
    out = mha_einsum(q, k, v, mask).reshape(b, sq, h * dh)
    return out @ p["wo"].astype(x.dtype)


def _project_enc_kv(cfg: ModelConfig, p: dict, enc_out):
    b, se, _ = enc_out.shape
    kv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    k = (enc_out @ p["wk"].astype(enc_out.dtype)).reshape(b, se, kv, dh)
    v = (enc_out @ p["wv"].astype(enc_out.dtype)).reshape(b, se, kv, dh)
    return k, v


def encode(cfg: ModelConfig, params, frames) -> jax.Array:
    """frames: [B, Se, d] stub embeddings -> encoder stream [B, Se, d]."""
    compute = jnp.dtype(cfg.compute_dtype)
    x = frames.astype(compute) + params["enc_pos"].astype(compute)[None]
    se = x.shape[1]
    positions = jnp.broadcast_to(
        jnp.arange(se, dtype=jnp.int32), (x.shape[0], se))
    for blk in params["enc_blocks"]:
        h = norm_apply(cfg, blk["norm1"], x)
        x = x + attention_apply(cfg, blk["attn"], h, positions=positions,
                                causal=False)
        x = x + mlp_apply(cfg, blk["mlp"], norm_apply(cfg, blk["norm2"], x))
    return norm_apply(cfg, params["enc_final"], x)


def encdec_forward(cfg: ModelConfig, params, tokens, extras,
                   dist: Optional[DistContext] = None):
    """Teacher-forced decoder over the full token sequence."""
    enc_out = encode(cfg, params, extras["frames"])
    compute = jnp.dtype(cfg.compute_dtype)
    b, s = tokens.shape
    # clamp into the learned table: whisper's real ctx is 448; the 32k shape
    # cells lower structurally with saturated positions beyond the table
    pos_idx = jnp.minimum(jnp.arange(s), _MAX_DECODE_POS - 1)
    pos_tab = jnp.take(params["dec_pos"].astype(compute), pos_idx, axis=0)
    x = jnp.take(params["embed"].astype(compute), tokens, axis=0) \
        + pos_tab[None]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    for blk in params["dec_blocks"]:
        h = norm_apply(cfg, blk["norm1"], x)
        x = x + attention_apply(cfg, blk["attn"], h, positions=positions,
                                causal=True)
        hx = norm_apply(cfg, blk["norm_x"], x)
        ek, ev = _project_enc_kv(cfg, blk["xattn"], enc_out)
        x = x + _cross_attend(cfg, blk["xattn"], hx, ek, ev)
        x = x + mlp_apply(cfg, blk["mlp"], norm_apply(cfg, blk["norm2"], x))
    x = norm_apply(cfg, params["dec_final"], x)
    logits = x @ params["embed"].T.astype(x.dtype)
    return logits, jnp.zeros((), jnp.float32)


def encdec_loss(cfg: ModelConfig, params, batch,
                dist: Optional[DistContext] = None):
    logits, aux = encdec_forward(cfg, params, batch["tokens"], batch, dist)
    logits = logits.astype(jnp.float32)
    labels = batch["labels"]
    m = logits.max(-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    ll = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    nll = (lse - ll).mean()
    metrics = {"loss": nll, "nll": nll, "aux": aux,
               "ppl_proxy": jnp.exp(jnp.minimum(nll, 20.0))}
    return nll, metrics


def encdec_init_cache(cfg: ModelConfig, batch: int, seq_len: int,
                      frames: Optional[jax.Array] = None,
                      params: Optional[dict] = None) -> Any:
    """Self-attn KV cache (head-major [B, K, seq_len, Dh]) + per-layer
    projected cross KV [B, encoder_len, K, Dh].

    With ``frames``+``params`` the cross cache holds the real encoder
    projections; otherwise zeros (structural lowering path passes the
    cache in as an input ShapeDtypeStruct anyway).
    """
    kv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    compute = jnp.dtype(cfg.compute_dtype)
    layers = []
    enc_out = None
    if frames is not None and params is not None:
        enc_out = encode(cfg, params, frames)
    for i in range(cfg.n_layers):
        entry = {
            "k": jnp.zeros((batch, kv, seq_len, dh), compute),
            "v": jnp.zeros((batch, kv, seq_len, dh), compute),
        }
        if enc_out is not None:
            ek, ev = _project_enc_kv(
                cfg, params["dec_blocks"][i]["xattn"], enc_out)
            entry["xk"], entry["xv"] = ek, ev
        else:
            entry["xk"] = jnp.zeros((batch, cfg.encoder_len, kv, dh), compute)
            entry["xv"] = jnp.zeros((batch, cfg.encoder_len, kv, dh), compute)
        layers.append(entry)
    return layers


def encdec_decode_step(cfg: ModelConfig, params, cache, tokens, pos,
                       dist: Optional[DistContext] = None):
    """tokens [B] -> (logits [B, V], cache); cross KV is static per request."""
    compute = jnp.dtype(cfg.compute_dtype)
    b = tokens.shape[0]
    pos_emb = jnp.take(params["dec_pos"],
                       jnp.minimum(pos, _MAX_DECODE_POS - 1), axis=0)
    x = jnp.take(params["embed"].astype(compute), tokens[:, None],
                 axis=0) + pos_emb.astype(compute)[None, None]
    new_cache = []
    for blk, cache_l in zip(params["dec_blocks"], cache):
        h = norm_apply(cfg, blk["norm1"], x)
        entry = dict(cache_l)
        attn, entry["k"], entry["v"] = attention_decode(
            cfg, blk["attn"], h, cache_l["k"], cache_l["v"], pos)
        x = x + attn
        hx = norm_apply(cfg, blk["norm_x"], x)
        x = x + _cross_attend(cfg, blk["xattn"], hx,
                              cache_l["xk"], cache_l["xv"])
        x = x + mlp_apply(cfg, blk["mlp"], norm_apply(cfg, blk["norm2"], x))
        new_cache.append(entry)
    x = norm_apply(cfg, params["dec_final"], x)
    logits = x @ params["embed"].T.astype(x.dtype)
    return logits[:, 0], new_cache
