"""Unified model API: ``build_model(cfg)`` -> init / loss / prefill / decode.

This is the surface the launcher, dry-run, trainer, and server consume;
every assigned architecture is reachable through it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.registry import ModelConfig
from . import encdec, transformer

__all__ = ["Model", "build_model", "input_specs"]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[jax.Array], Any]
    loss: Callable[..., Any]          # (params, batch, dist) -> (loss, metrics)
    prefill: Callable[..., Any]       # (params, batch, dist[, cache_len])
                                      #   -> (logits, cache)
    init_cache: Callable[..., Any]    # (batch, seq_len) -> cache
    decode_step: Callable[..., Any]   # (params, cache, tokens, pos, dist)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.encdec:
        return Model(
            cfg=cfg,
            init=lambda key: encdec.init_encdec(key, cfg),
            loss=lambda params, batch, dist=None: encdec.encdec_loss(
                cfg, params, batch, dist),
            prefill=lambda params, batch, dist=None: encdec.encdec_forward(
                cfg, params, batch["tokens"], batch, dist),
            init_cache=lambda batch, seq_len: encdec.encdec_init_cache(
                cfg, batch, seq_len),
            decode_step=lambda params, cache, tokens, pos, dist=None:
                encdec.encdec_decode_step(cfg, params, cache, tokens, pos,
                                          dist),
        )
    return Model(
        cfg=cfg,
        init=lambda key: transformer.init_lm(key, cfg),
        loss=lambda params, batch, dist=None: transformer.lm_loss(
            cfg, params, batch, dist),
        prefill=lambda params, batch, dist=None, cache_len=None:
            transformer.lm_prefill(cfg, params, batch["tokens"], batch, dist,
                                   cache_len=cache_len),
        init_cache=lambda batch, seq_len: transformer.init_decode_cache(
            cfg, batch, seq_len),
        decode_step=lambda params, cache, tokens, pos, dist=None:
            transformer.lm_decode_step(cfg, params, cache, tokens, pos, dist),
    )


def input_specs(cfg: ModelConfig, kind: str, seq_len: int,
                global_batch: int) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell.

    Weak-type-correct, shardable, no device allocation -- the dry-run
    lowers against these.  ``decode`` kinds return the *step* inputs
    (tokens + pos); the cache is built separately via ``Model.init_cache``.
    """
    f32 = jnp.dtype(cfg.compute_dtype)
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    b, s = global_batch, seq_len
    if kind in ("train", "prefill"):
        batch = {
            "tokens": sds((b, s), i32),
            "labels": sds((b, s), i32),
        }
        if cfg.frontend == "vision_stub":
            batch["patch_embeds"] = sds((b, cfg.frontend_len, cfg.d_model),
                                        f32)
        if cfg.frontend == "audio_stub":
            batch["frames"] = sds((b, cfg.encoder_len, cfg.d_model), f32)
        return batch
    if kind == "decode":
        return {"tokens": sds((b,), i32),
                "pos": sds((), i32)}
    raise ValueError(f"unknown shape kind {kind!r}")
