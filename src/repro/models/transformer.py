"""Decoder-only LM assembly for every non-enc-dec family.

One block vocabulary ("dense" | "moe" | "hybrid" | "m" | "s"), three
execution modes (train forward, prefill-with-cache, decode step), one
parameter layout rule: homogeneous stacks are scanned (``cfg.scan_layers``)
with remat, heterogeneous stacks (xlstm patterns, hymba's mixed cache
shapes) are unrolled lists.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import scopes
from ..configs.registry import ModelConfig
from .dist import DistContext
from .layers import (
    assemble_kv_cache,
    attention_apply,
    attention_decode,
    embed_init,
    init_attention,
    init_mlp,
    init_norm,
    mlp_apply,
    norm_apply,
)
from .moe import init_moe, moe_apply
from .sharding import logical_constraint
from .ssm import (
    init_mamba, init_mlstm, init_slstm,
    mamba_apply, mamba_decode, mamba_zero_state,
    mlstm_apply, mlstm_decode, mlstm_zero_state,
    slstm_apply, slstm_decode, slstm_zero_state,
)

__all__ = [
    "layer_kinds", "init_lm", "lm_forward", "lm_loss",
    "init_decode_cache", "lm_decode_step", "lm_prefill",
]


# ---------------------------------------------------------------------------
# block vocabulary
# ---------------------------------------------------------------------------

def layer_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    if cfg.family == "ssm":
        assert cfg.block_pattern and len(cfg.block_pattern) == cfg.n_layers
        return tuple(cfg.block_pattern)
    if cfg.family == "hybrid":
        return ("hybrid",) * cfg.n_layers
    if cfg.family == "moe":
        return ("moe",) * cfg.n_layers
    return ("dense",) * cfg.n_layers


def _init_block(key, cfg: ModelConfig, kind: str) -> dict:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    pdt = jnp.dtype(cfg.param_dtype)
    if kind == "m":
        return {"norm1": init_norm(cfg, d),
                "mlstm": init_mlstm(ks[0], cfg, pdt)}
    if kind == "s":
        return {"norm1": init_norm(cfg, d),
                "slstm": init_slstm(ks[0], cfg, pdt)}
    p = {
        "norm1": init_norm(cfg, d),
        "attn": init_attention(ks[0], cfg, pdt),
        "norm2": init_norm(cfg, d),
    }
    if kind == "moe":
        p["moe"] = init_moe(ks[1], cfg, pdt)
    elif kind == "hybrid":
        p["mamba"] = init_mamba(ks[1], cfg, pdt)
        p["fuse_norm_attn"] = init_norm(cfg, d)
        p["fuse_norm_ssm"] = init_norm(cfg, d)
        p["mlp"] = init_mlp(ks[2], cfg, dtype=pdt)
    else:
        p["mlp"] = init_mlp(ks[1], cfg, dtype=pdt)
    return p


def _window_args(cfg: ModelConfig, full_flag) -> Tuple[Optional[int], Any]:
    """(window size or None, traced/static use_window flag)."""
    if cfg.swa_window is None:
        return None, False
    if isinstance(full_flag, bool):
        return (None, False) if full_flag else (cfg.swa_window, True)
    # traced flag (scan over layers): window masked dynamically
    return cfg.swa_window, jnp.logical_not(full_flag)


@scopes.scoped(scopes.TRANSFORMER_BLOCK)
def _block_train(cfg: ModelConfig, p: dict, x, *, positions, dist,
                 kind: str, full_flag, emit_cache: bool = False,
                 cache_len: int = 0):
    """Returns (x, aux) or, with emit_cache, (x, aux, cache_entry)."""
    aux = jnp.zeros((), jnp.float32)
    cache = None
    if kind in ("m", "s"):
        apply = mlstm_apply if kind == "m" else slstm_apply
        key = "mlstm" if kind == "m" else "slstm"
        h = norm_apply(cfg, p["norm1"], x)
        if emit_cache:
            y, st = apply(cfg, p[key], h, return_state=True)
            cache = {"state": st}
        else:
            y = apply(cfg, p[key], h)
        x = x + y
        return (x, aux, cache) if emit_cache else (x, aux)
    window, use_window = _window_args(cfg, full_flag)
    h = norm_apply(cfg, p["norm1"], x)
    attn_out = attention_apply(cfg, p["attn"], h, positions=positions,
                               window=window, use_window=use_window,
                               return_kv=emit_cache)
    if emit_cache:
        attn_out, (k_raw, v_raw) = attn_out
        # ring/window semantics must match init_decode_cache for this layer
        is_full = full_flag if isinstance(full_flag, bool) else False
        cache_window = None if (cfg.swa_window is None or is_full) \
            else cfg.swa_window
        k_c, v_c = assemble_kv_cache(k_raw, v_raw, cache_window, cache_len)
        cache = {"k": k_c, "v": v_c}
    if kind == "hybrid":
        if emit_cache:
            ssm, st = mamba_apply(cfg, p["mamba"], h, return_state=True)
            cache["ssm"] = st
        else:
            ssm = mamba_apply(cfg, p["mamba"], h)
        fused = 0.5 * (norm_apply(cfg, p["fuse_norm_attn"], attn_out)
                       + norm_apply(cfg, p["fuse_norm_ssm"], ssm))
        x = x + fused
    else:
        x = x + attn_out
    h2 = norm_apply(cfg, p["norm2"], x)
    if kind == "moe":
        y, aux = moe_apply(cfg, p["moe"], h2, dist)
        x = x + y
    else:
        x = x + mlp_apply(cfg, p["mlp"], h2)
    return (x, aux, cache) if emit_cache else (x, aux)


# ---------------------------------------------------------------------------
# params assembly
# ---------------------------------------------------------------------------

def init_lm(key, cfg: ModelConfig) -> dict:
    kinds = layer_kinds(cfg)
    pdt = jnp.dtype(cfg.param_dtype)
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": embed_init(k_embed, cfg.vocab, cfg.d_model, pdt),
        "final_norm": init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(
            k_head, cfg.vocab, cfg.d_model, pdt).T  # [d, V]
    keys = jax.random.split(k_blocks, cfg.n_layers)
    if cfg.scan_layers:
        assert len(set(kinds)) == 1, "scan requires homogeneous blocks"
        params["blocks"] = jax.vmap(
            lambda k: _init_block(k, cfg, kinds[0]))(keys)
    else:
        params["blocks"] = [
            _init_block(keys[i], cfg, kinds[i]) for i in range(cfg.n_layers)]
    return params


def _full_flags(cfg: ModelConfig) -> jnp.ndarray:
    flags = [i in cfg.full_attn_layers for i in range(cfg.n_layers)]
    return jnp.array(flags)


def _embed_tokens(cfg: ModelConfig, params, tokens, extras) -> jax.Array:
    compute = jnp.dtype(cfg.compute_dtype)
    # cast the table BEFORE the gather: the vocab-sharded lookup lowers to
    # masked-select + all-reduce over "model", which must ride in bf16
    x = jnp.take(params["embed"].astype(compute), tokens, axis=0)
    if cfg.frontend == "vision_stub" and extras is not None:
        fl = cfg.frontend_len
        patch = extras["patch_embeds"].astype(compute)
        x = jnp.concatenate([patch, x[:, fl:]], axis=1) \
            if x.shape[1] > fl else patch[:, :x.shape[1]]
    return logical_constraint(x, "batch", "act_seq", "model_dim")


@scopes.scoped(scopes.LM_HEAD)
def _lm_logits(cfg: ModelConfig, params, x) -> jax.Array:
    x = norm_apply(cfg, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    return logical_constraint(logits, "batch", "act_seq", "vocab")


# ---------------------------------------------------------------------------
# training forward / loss
# ---------------------------------------------------------------------------

def lm_forward(cfg: ModelConfig, params, tokens, extras=None,
               dist: Optional[DistContext] = None):
    """tokens [B, S] -> (logits [B, S, V], aux)."""
    b, s = tokens.shape
    x = _embed_tokens(cfg, params, tokens, extras)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    kinds = layer_kinds(cfg)
    if cfg.scan_layers:
        flags = _full_flags(cfg)

        def body(carry, inp):
            xx, aux_total = carry
            p_l, flag_l = inp
            xx, aux = _block_train(cfg, p_l, xx, positions=positions,
                                   dist=dist, kind=kinds[0],
                                   full_flag=flag_l)
            return (xx, aux_total + aux), None

        carry0 = (x, jnp.zeros((), jnp.float32))
        g = cfg.remat_group
        if cfg.remat and g and cfg.n_layers % g == 0:
            # two-level remat: only n_layers/g group-boundary carries are
            # saved; each group's layers recompute twice in the backward.
            # Cuts saved-activation memory ~g-fold for +1 extra forward.
            inner = jax.checkpoint(body)

            def group(carry, inp):
                return jax.lax.scan(inner, carry, inp)

            n_groups = cfg.n_layers // g
            grouped = jax.tree.map(
                lambda a: a.reshape((n_groups, g) + a.shape[1:]),
                (params["blocks"], flags))
            (x, aux), _ = jax.lax.scan(jax.checkpoint(group), carry0,
                                       grouped)
        else:
            body = jax.checkpoint(body) if cfg.remat else body
            (x, aux), _ = jax.lax.scan(body, carry0,
                                       (params["blocks"], flags))
    else:
        aux = jnp.zeros((), jnp.float32)
        for i, p_l in enumerate(params["blocks"]):
            fn = partial(_block_train, cfg, kind=kinds[i], dist=dist,
                         full_flag=i in cfg.full_attn_layers)
            if cfg.remat:
                fn = jax.checkpoint(fn)
            x, a = fn(p_l, x, positions=positions)
            aux = aux + a
    return _lm_logits(cfg, params, x), aux


def lm_loss(cfg: ModelConfig, params, batch,
            dist: Optional[DistContext] = None):
    """batch: {"tokens": [B,S], "labels": [B,S], extras...}."""
    logits, aux = lm_forward(cfg, params, batch["tokens"], batch, dist)
    labels = batch["labels"]
    if cfg.bf16_ce:
        # beyond-paper memory knob: never materialize an f32 [B,S,V]
        # tensor -- max/exp stay bf16, only the V-reduction accumulates in
        # f32 (rel. lse error ~3e-3, amortized to zero by normalization).
        m = logits.max(-1, keepdims=True)
        expv = jnp.exp((logits - m))                      # bf16
        denom = jnp.sum(expv, axis=-1, dtype=jnp.float32)
        lse = m[..., 0].astype(jnp.float32) + jnp.log(denom)
        label_logit = jnp.take_along_axis(
            logits, labels[..., None].astype(jnp.int32),
            axis=-1)[..., 0].astype(jnp.float32)
    else:
        logits32 = logits.astype(jnp.float32)
        m = logits32.max(-1, keepdims=True)
        lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits32 - m), axis=-1))
        label_logit = jnp.take_along_axis(
            logits32, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    nll = (lse - label_logit).mean()
    loss = nll + 0.01 * aux
    metrics = {"loss": loss, "nll": nll, "aux": aux,
               "ppl_proxy": jnp.exp(jnp.minimum(nll, 20.0))}
    return loss, metrics


# ---------------------------------------------------------------------------
# decode: cache init, prefill, single step
# ---------------------------------------------------------------------------

def _phys_len(cfg: ModelConfig, seq_len: int, full_attn: bool) -> int:
    if cfg.swa_window is None or full_attn:
        return seq_len
    return min(seq_len, cfg.swa_window)


def _zero_cache_block(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                      full_attn: bool) -> dict:
    kv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    compute = jnp.dtype(cfg.compute_dtype)
    if kind == "m":
        return {"state": mlstm_zero_state(cfg, batch)}
    if kind == "s":
        return {"state": slstm_zero_state(cfg, batch)}
    phys = _phys_len(cfg, seq_len, full_attn)
    c = {
        "k": jnp.zeros((batch, phys, kv, dh), compute),
        "v": jnp.zeros((batch, phys, kv, dh), compute),
    }
    if kind == "hybrid":
        c["ssm"] = mamba_zero_state(cfg, batch)
    return c


def init_decode_cache(cfg: ModelConfig, batch: int, seq_len: int) -> Any:
    kinds = layer_kinds(cfg)
    if cfg.scan_layers:
        one = _zero_cache_block(cfg, kinds[0], batch, seq_len,
                                full_attn=False)
        if cfg.full_attn_layers:
            # mixed window/full caches cannot stack; use full-size everywhere
            one = _zero_cache_block(cfg, kinds[0], batch, seq_len, True)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape),
            one)
    return [
        _zero_cache_block(cfg, kinds[i], batch, seq_len,
                          full_attn=i in cfg.full_attn_layers)
        for i in range(cfg.n_layers)]


@scopes.scoped(scopes.TRANSFORMER_BLOCK)
def _block_decode(cfg: ModelConfig, p: dict, cache: dict, x, pos, *,
                  kind: str, full_flag, dist) -> Tuple[jax.Array, dict]:
    if kind == "m":
        y, st = mlstm_decode(cfg, p["mlstm"],
                             norm_apply(cfg, p["norm1"], x), cache["state"])
        return x + y, {"state": st}
    if kind == "s":
        y, st = slstm_decode(cfg, p["slstm"],
                             norm_apply(cfg, p["norm1"], x), cache["state"])
        return x + y, {"state": st}
    window = None
    if cfg.swa_window is not None:
        is_full = full_flag if isinstance(full_flag, bool) else False
        phys = cache["k"].shape[1]
        # ring semantics engage only when the cache is window-sized
        window = cfg.swa_window if (not is_full and
                                    phys <= cfg.swa_window) else None
    h = norm_apply(cfg, p["norm1"], x)
    new_cache = dict(cache)
    attn, new_cache["k"], new_cache["v"] = attention_decode(
        cfg, p["attn"], h, cache["k"], cache["v"], pos, window=window)
    if kind == "hybrid":
        ssm, new_cache["ssm"] = mamba_decode(cfg, p["mamba"], h, cache["ssm"])
        x = x + 0.5 * (norm_apply(cfg, p["fuse_norm_attn"], attn)
                       + norm_apply(cfg, p["fuse_norm_ssm"], ssm))
    else:
        x = x + attn
    h2 = norm_apply(cfg, p["norm2"], x)
    if kind == "moe":
        y, _ = moe_apply(cfg, p["moe"], h2, dist)
        x = x + y
    else:
        x = x + mlp_apply(cfg, p["mlp"], h2)
    return x, new_cache


def lm_decode_step(cfg: ModelConfig, params, cache, tokens, pos,
                   dist: Optional[DistContext] = None):
    """tokens [B] int32, pos scalar int32 -> (logits [B, V], new cache)."""
    compute = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"].astype(compute), tokens[:, None], axis=0)
    kinds = layer_kinds(cfg)
    if cfg.scan_layers:
        flags = _full_flags(cfg)

        def body(xx, inp):
            p_l, cache_l, flag_l = inp
            xx, new_cache_l = _block_decode(
                cfg, p_l, cache_l, xx, pos, kind=kinds[0],
                full_flag=flag_l, dist=dist)
            return xx, new_cache_l

        # XLA slices each layer's cache out of the stack and writes it
        # back around the body: that is cache traffic too.
        with jax.named_scope(scopes.ATTENTION_KV_CACHE):
            x, new_cache = jax.lax.scan(body, x,
                                        (params["blocks"], cache, flags))
    else:
        new_cache = []
        for i, (p_l, cache_l) in enumerate(zip(params["blocks"], cache)):
            x, c = _block_decode(cfg, p_l, cache_l, x, pos, kind=kinds[i],
                                 full_flag=i in cfg.full_attn_layers,
                                 dist=dist)
            new_cache.append(c)
    logits = _lm_logits(cfg, params, x)
    return logits[:, 0], new_cache


def lm_prefill(cfg: ModelConfig, params, tokens, extras=None,
               dist: Optional[DistContext] = None,
               cache_len: Optional[int] = None):
    """Forward over the full prompt, emitting a decode-ready cache.

    Returns (last-position logits [B, V], cache); decode continues at
    pos = S.  ``cache_len`` sizes the cache (prompt + generation budget,
    default = prompt length).  Windowed layers emit ring-aligned window
    caches (slot p % window holds position p).
    """
    b, s = tokens.shape
    cache_len = cache_len or s
    assert cache_len >= s, "cache must at least hold the prompt"
    x = _embed_tokens(cfg, params, tokens, extras)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    kinds = layer_kinds(cfg)
    if cfg.scan_layers:
        flags = _full_flags(cfg)
        # mixed full/window layers cannot stack ring caches: treat all as
        # full-size (matches init_decode_cache's scan branch)
        eff_cfg = cfg
        if cfg.full_attn_layers and cfg.swa_window is not None:
            eff_cfg = dataclasses.replace(cfg, swa_window=None)

        def body(xx, inp):
            p_l, flag_l = inp
            xx, _, cache_l = _block_train(
                eff_cfg, p_l, xx, positions=positions, dist=dist,
                kind=kinds[0], full_flag=flag_l, emit_cache=True,
                cache_len=cache_len)
            return xx, cache_l

        # XLA writes each layer's cache into the stack around the body
        with jax.named_scope(scopes.ATTENTION_KV_CACHE):
            x, cache = jax.lax.scan(body, x, (params["blocks"], flags))
    else:
        cache = []
        for i, p_l in enumerate(params["blocks"]):
            x, _, cache_l = _block_train(
                cfg, p_l, x, positions=positions, dist=dist, kind=kinds[i],
                full_flag=i in cfg.full_attn_layers, emit_cache=True,
                cache_len=cache_len)
            cache.append(cache_l)
    logits = _lm_logits(cfg, params, x[:, -1:])
    return logits[:, 0], cache
