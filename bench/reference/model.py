"""Plain float32 forward of the served decoder, layer by layer.

Independent of the program under test: it imports nothing from ``src/``
and takes nothing the program made.  Its weights come from
``bench/weights.py`` and the seed, its sizes from the configuration file.

What is common to the served decoders is here: the token embedding, a
walk over the layers in order, the final RMSNorm and the LM head, and the
routing groups of the served calls.  What one kind of layer computes, and
how it routes, is its architecture module's (``bench/arch``), which
``Reference`` is given: the configuration names it.

Every matrix product runs at ``precision=HIGHEST``, in float32.  With
``low="e4m3"`` both operands of every product are first rounded to
float8-e4m3 (per-row and per-column scales): the control, the nearest
precision below the bfloat16 the configuration serves in.  With
``low="bf16"`` they are rounded to bfloat16 instead: the served precision,
a witness of how far rounding alone moves the served tokens.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights

HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def e4m3(x, axis: int):
    """Round to float8-e4m3 with one scale per slice along ``axis``
    (the slice's largest magnitude maps to 448), back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / _E4M3_MAX, 1.0)
    y = x / scale
    # 3 mantissa bits; below 2**-6 the subnormal step 2**-9.
    exp = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    step = 2.0 ** (exp - 3)
    y = jnp.clip(jnp.round(y / step) * step, -_E4M3_MAX, _E4M3_MAX)
    return y * scale


def lower(x, axis: int, low: Optional[str]):
    """``x`` rounded to the precision ``low`` names (None: as it is)."""
    if low == "e4m3":
        return e4m3(x, axis)
    if low == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if low is not None:
        raise ValueError(f"unknown precision {low!r}")
    return x


def matmul(a, b, low: Optional[str]):
    """a [..., k] @ b [k, n] in float32 at HIGHEST (operands rounded to
    ``low`` first)."""
    a = lower(a.astype(jnp.float32), -1, low)
    b = lower(b.astype(jnp.float32), 0, low)
    return jnp.matmul(a, b, precision=HIGHEST)


def rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * scale.astype(jnp.float32)


def rope(x, positions, theta: float):
    """x [T, H, dh], positions [T]: rotate the two halves of each head."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def routing_groups(batch: int, seq: int, prompt_len: int, n_shards: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Group id of every token of a [batch, seq] forward that stands for
    one prefill of ``prompt_len`` tokens and then one decode call per
    further position, each call split over ``n_shards`` source shards
    (contiguous row blocks); and each group's token count."""
    rows = batch // n_shards
    b = np.arange(batch)[:, None]
    s = np.arange(seq)[None, :]
    call = np.where(s < prompt_len, 0, s - prompt_len + 1)
    group = call * n_shards + b // rows
    n_groups = (seq - prompt_len + 1) * n_shards
    sizes = np.bincount(group.reshape(-1), minlength=n_groups)
    return group.reshape(-1).astype(np.int32), sizes


class Reference:
    """The plain forward of one configuration: ``arch`` is its
    architecture module, ``dims`` its sizes.  ``makers`` (one
    ``weights.LayerMaker`` per layer stack) may be shared between
    references in other precisions."""

    def __init__(self, arch, dims: dict, *, low: Optional[str] = None,
                 makers: Optional[Dict[str, object]] = None):
        self.arch = arch
        self.dims = dims
        self.low = low
        self.tree = arch.param_tree(dims)
        self.layers = arch.layers(dims)
        self.makers = makers or {
            s: weights.LayerMaker(self.tree, s)
            for s in dict.fromkeys(s for s, _, _ in self.layers)}
        self._fns: Dict[tuple, object] = {}

    def _layer_fn(self, kind: str, static: dict):
        key = (kind, tuple(sorted(static.items())))
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = jax.jit(functools.partial(
                self.arch.KINDS[kind], self.dims, low=self.low, **static))
        return fn

    def globals(self, seed: int, device=None) -> Dict:
        """The leaves outside the layer stacks, on ``device``."""
        return weights.make_globals(self.tree, self.makers, seed,
                                    device=device)

    def hidden(self, seed: int, jobs: Sequence[dict]):
        """Final hidden states of several forwards, layer by layer.

        Each job is ``{"device", "embed", "tokens" [B, T], "prompt_len",
        "n_shards"}``; jobs on different devices run side by side (the
        layer's weights are made on each job's device).  Returns one
        ``(hidden [B, T, d], dropped assignments)`` per job."""
        state = []
        for job in jobs:
            bsz, seq = job["tokens"].shape
            group, sizes = routing_groups(bsz, seq, job["prompt_len"],
                                          job["n_shards"])
            args, static = self.arch.routing(self.dims, sizes)
            dev = job["device"]
            x = jnp.take(job["embed"], jax.device_put(job["tokens"], dev),
                         axis=0).astype(jnp.float32)
            state.append([x, jax.device_put((group, *args), dev), static,
                          0])
        for stack, index, kind in self.layers:
            for job, st in zip(jobs, state):
                w = self.makers[stack](seed, index, device=job["device"])
                st[0], drops = self._layer_fn(kind, st[2])(w, st[0], *st[1])
                st[3] = st[3] + drops
                del w
        return [(st[0], int(st[3])) for st in state]


def head(g: Dict):
    """(final norm scale, LM head [d, V]) of the globals: the embedding's
    transpose where the configuration ties them."""
    lm_head = g["lm_head"] if "lm_head" in g else g["embed"].T
    return g["final_norm"]["scale"], lm_head


@functools.partial(jax.jit, static_argnames=("low",))
def score(final_scale, lm_head, hidden, tokens, low: Optional[str] = None):
    """For hidden [R, P, d] and candidate tokens [R, P, m]: the best
    logit, the argmax, and the logits of the candidates, per position."""

    def one(args):
        h, cand = args
        logits = matmul(rmsnorm(h, final_scale), lm_head, low)   # [P, V]
        return (logits.max(-1), jnp.argmax(logits, -1),
                jnp.take_along_axis(logits, cand, axis=-1))

    return jax.lax.map(one, (hidden, tokens))


def served_positions(prompt_len: int, gen_len: int) -> np.ndarray:
    """Positions whose logits chose the served tokens."""
    return np.arange(prompt_len - 1, prompt_len - 1 + gen_len)


def forward_tokens(prompts: np.ndarray, served: np.ndarray) -> np.ndarray:
    """The sequence the reference runs: each prompt followed by its served
    tokens but the last (which no position was fed)."""
    return np.concatenate([prompts, served[:, :-1]], axis=1)


def gaps(ref_best, ref_at) -> np.ndarray:
    """How far below the reference's best logit each chosen token lies."""
    return np.asarray(ref_best)[..., None] - np.asarray(ref_at)
