"""Seeded weights for the served model, made on the device in one jit.

The benchmark, not the program, makes the weights, so that the plain
reference can make the very same numbers again from the seed alone.  The
layout is the served model's parameter tree (scan-stacked blocks); the
harness checks it against the program's own ``init`` shapes before use.

Every leaf draws from a key folded from the root key, the leaf's path and
its stacked indices (layer, then expert), so one layer, or one expert of
one layer, can be regenerated on its own.  Values follow the usual
initialisation: projections N(0, 1/fan_in), embeddings and LM head
N(0, 0.02^2), norm scales one; each drawn in float32 and stored in the
leaf's dtype.
"""

from __future__ import annotations

import zlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def block_shapes(dims: dict, stacked: bool = True) -> dict:
    """Shapes of one MoE block's leaves (with a leading layer axis when
    ``stacked``)."""
    d, h, kv = dims["d_model"], dims["n_heads"], dims["n_kv_heads"]
    dh = dims.get("head_dim") or d // h
    f, e = dims["d_ff"], dims["num_experts"]
    pdt = dims["param_dtype"]
    lead = (dims["n_layers"],) if stacked else ()
    return {
        "norm1": {"scale": _sds(lead + (d,), "float32")},
        "attn": {"wq": _sds(lead + (d, h * dh), pdt),
                 "wk": _sds(lead + (d, kv * dh), pdt),
                 "wv": _sds(lead + (d, kv * dh), pdt),
                 "wo": _sds(lead + (h * dh, d), pdt)},
        "norm2": {"scale": _sds(lead + (d,), "float32")},
        "moe": {"router": _sds(lead + (d, e), "float32"),
                "w_gate": _sds(lead + (e, d, f), pdt),
                "w_up": _sds(lead + (e, d, f), pdt),
                "w_down": _sds(lead + (e, f, d), pdt)},
    }


def param_shapes(dims: dict) -> dict:
    d, v, pdt = dims["d_model"], dims["vocab"], dims["param_dtype"]
    return {"embed": _sds((v, d), pdt),
            "final_norm": {"scale": _sds((d,), "float32")},
            "lm_head": _sds((d, v), pdt),
            "blocks": block_shapes(dims)}


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _n_stacked(path: str) -> int:
    """Stacked leading axes of a leaf: layer, and expert for expert FFNs."""
    if not path.startswith("blocks/"):
        return 0
    return 2 if path.split("/")[-1] in ("w_gate", "w_up", "w_down") else 1


def _draw(key, path: str, shape, dtype):
    name = path.split("/")[-1]
    if name == "scale":
        return jnp.ones(shape, dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    if name in ("embed", "lm_head"):
        return (x * 0.02).astype(dtype)
    return (x * float(1.0 / np.sqrt(shape[-2]))).astype(dtype)


def _leaf(root, path: str, sds, fixed=()):
    """One leaf; ``fixed`` pins its first stacked indices (layer, ...)."""
    key = jax.random.fold_in(root, zlib.crc32(path.encode()))
    for i in fixed:
        key = jax.random.fold_in(key, i)
    n_free = _n_stacked(path) - len(fixed)
    shape = tuple(sds.shape)
    inner = shape[n_free:]

    def draw(k):
        return _draw(k, path, inner, sds.dtype)

    for axis in reversed(range(n_free)):
        draw = (lambda f, n: lambda k: jax.vmap(
            lambda i: f(jax.random.fold_in(k, i)))(jnp.arange(n)))(
                draw, shape[axis])
    return draw(key)


def root_key(seed: int):
    """A jax key from a seed of any size (SeedSequence folds it down)."""
    state = np.random.SeedSequence([int(seed), 0x5EED]).generate_state(1)
    return jax.random.key(int(state[0]) & 0x7FFFFFFF)


def make_params(dims: dict, seed: int, out_shardings=None, device=None):
    """The whole parameter tree, in one jitted call on the device."""
    shapes = param_shapes(dims)

    def build(root):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: _leaf(root, _path(p), s), shapes)

    key = root_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build, out_shardings=out_shardings)(key)


class LayerMaker:
    """Regenerates one block's leaves by layer index, with one compiled
    program for every layer (the index is an argument, not a constant)."""

    def __init__(self, dims: dict):
        shapes = block_shapes(dims, stacked=False)

        def build(root, layer):
            return jax.tree_util.tree_map_with_path(
                lambda p, s: _leaf(root, "blocks/" + _path(p), s,
                                   fixed=(layer,)), shapes)

        self._build = jax.jit(build)

    def __call__(self, seed: int, layer: int, device=None) -> Dict:
        key, idx = root_key(seed), jnp.int32(layer)
        if device is not None:
            key, idx = jax.device_put((key, idx), device)
        return self._build(key, idx)


def make_globals(dims: dict, seed: int, device=None) -> Dict:
    """The leaves outside the blocks: embed, final norm, LM head."""
    shapes = {k: v for k, v in param_shapes(dims).items() if k != "blocks"}

    def build(root):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: _leaf(root, _path(p), s), shapes)

    key = root_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)


def check_layout(program_shapes, dims: dict) -> Optional[str]:
    """None when the program's parameter tree matches this layout, else
    what differs (the harness refuses to run then)."""
    ours = param_shapes(dims)
    a = {_path(p): (tuple(s.shape), jnp.dtype(s.dtype)) for p, s in
         jax.tree_util.tree_flatten_with_path(program_shapes)[0]}
    b = {_path(p): (tuple(s.shape), jnp.dtype(s.dtype)) for p, s in
         jax.tree_util.tree_flatten_with_path(ours)[0]}
    if a == b:
        return None
    diff = sorted(set(a.items()) ^ set(b.items()))
    return f"parameter layout differs from the benchmark's: {diff[:6]}"
