"""Seeded weights for the served model, made on the device in one jit.

The benchmark, not the program, makes the weights, so that the plain
reference can make the very same numbers again from the seed alone.  The
layout is the served model's parameter tree, as the configuration's
architecture module (``bench/arch``) declares it: a tree of ``Leaf``s,
each with its shape, its dtype, how many of its leading axes are stacked
(layer, then expert) and how it is initialised.  The harness checks the
layout against the program's own ``init`` shapes before use.

Every leaf draws from a key folded from the root key, the leaf's path and
its stacked indices (layer, then expert), so one layer, or one expert of
one layer, can be regenerated on its own.  Each leaf is drawn in float32
and stored in its dtype.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One parameter leaf as an architecture module declares it.

    ``stacked`` leading axes index the layer (and then the expert) that a
    slice belongs to; each index folds into the slice's key.  ``init`` is
    ``"fan_in"`` (N(0, 1/fan_in), fan-in the second-last axis), ``"embed"``
    (N(0, 0.02^2)) or ``"ones"``."""

    shape: Tuple[int, ...]
    dtype: str
    stacked: int = 0
    init: str = "fan_in"


def stack(tree, n: int):
    """``tree``'s leaves with a leading axis of ``n`` more stacked
    slices (the layers of a scanned stack, the experts of a layer)."""
    return jax.tree.map(lambda s: dataclasses.replace(
        s, shape=(n,) + tuple(s.shape), stacked=s.stacked + 1), tree,
        is_leaf=_is_leaf)


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def shapes(tree):
    """The tree as ``ShapeDtypeStruct``s."""
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        tuple(s.shape), jnp.dtype(s.dtype)), tree, is_leaf=_is_leaf)


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _draw(key, leaf: Leaf, shape):
    if leaf.init == "ones":
        return jnp.ones(shape, leaf.dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    if leaf.init == "embed":
        return (x * 0.02).astype(leaf.dtype)
    if leaf.init == "fan_in":
        return (x * float(1.0 / np.sqrt(shape[-2]))).astype(leaf.dtype)
    raise ValueError(f"unknown init {leaf.init!r}")


def _leaf(root, path: str, leaf: Leaf, fixed=()):
    """One leaf; ``fixed`` pins its first stacked indices (layer, ...) and
    ``leaf.shape`` then holds only the axes after them."""
    key = jax.random.fold_in(root, zlib.crc32(path.encode()))
    for i in fixed:
        key = jax.random.fold_in(key, i)
    n_free = leaf.stacked - len(fixed)
    shape = tuple(leaf.shape)
    inner = shape[n_free:]

    def draw(k):
        return _draw(k, leaf, inner)

    for axis in reversed(range(n_free)):
        draw = (lambda f, n: lambda k: jax.vmap(
            lambda i: f(jax.random.fold_in(k, i)))(jnp.arange(n)))(
                draw, shape[axis])
    return draw(key)


def root_key(seed: int):
    """A jax key from a seed of any size (SeedSequence folds it down)."""
    state = np.random.SeedSequence([int(seed), 0x5EED]).generate_state(1)
    return jax.random.key(int(state[0]) & 0x7FFFFFFF)


def make_params(tree, seed: int, out_shardings=None, device=None):
    """The whole parameter tree, in one jitted call on the device."""
    def build(root):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: _leaf(root, _path(p), s), tree, is_leaf=_is_leaf)

    key = root_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build, out_shardings=out_shardings)(key)


def make_globals(tree, stacks: Iterable[str], seed: int,
                 device=None) -> Dict:
    """The leaves outside the layer stacks (embedding, final norm, head)."""
    stacks = set(stacks)
    return make_params({k: v for k, v in tree.items() if k not in stacks},
                       seed, device=device)


class LayerMaker:
    """Regenerates one layer of the stack ``tree[name]`` by its index,
    with one compiled program for every layer (the index is an argument,
    not a constant)."""

    def __init__(self, tree, name: str):
        one = jax.tree.map(lambda s: dataclasses.replace(
            s, shape=tuple(s.shape)[1:]), tree[name], is_leaf=_is_leaf)

        def build(root, layer):
            return jax.tree_util.tree_map_with_path(
                lambda p, s: _leaf(root, f"{name}/" + _path(p), s,
                                   fixed=(layer,)), one, is_leaf=_is_leaf)

        self._build = jax.jit(build)

    def __call__(self, seed: int, layer: int, device=None) -> Dict:
        key, idx = root_key(seed), jnp.int32(layer)
        if device is not None:
            key, idx = jax.device_put((key, idx), device)
        return self._build(key, idx)


def check_layout(program_shapes, tree) -> Optional[str]:
    """None when the program's parameter tree matches this layout, else
    what differs (the harness refuses to run then)."""
    a = {_path(p): (tuple(s.shape), jnp.dtype(s.dtype)) for p, s in
         jax.tree_util.tree_flatten_with_path(program_shapes)[0]}
    b = {_path(p): (tuple(s.shape), jnp.dtype(s.dtype)) for p, s in
         jax.tree_util.tree_flatten_with_path(shapes(tree))[0]}
    if a == b:
        return None
    diff = sorted(set(a.items()) ^ set(b.items()))
    return f"parameter layout differs from the benchmark's: {diff[:6]}"
