"""Plain float32 forward of the served MoE decoder.

Independent of the program under test: it imports nothing from ``src/``
and takes nothing the program made.  Its weights come from
``bench/weights.py`` and the seed, its sizes from the configuration file.

The model, as the configuration states it: token embedding; per block
RMSNorm, grouped-query attention with rotary positions (halves rotated,
causal, banded when a window is set), RMSNorm, a top-k mixture of SwiGLU
experts with a softmax router whose k gates are renormalised; final
RMSNorm and LM head.  The experts keep the capacity semantics of an
expert-parallel deployment: tokens are routed in groups (one group per
source shard and call), each expert takes at most ``capacity`` of a
group's assignments, first come (in batch-major token order) first
served, and an assignment past the capacity contributes nothing.

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

HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def capacity(capacity_factor: float, n_tokens: int, top_k: int,
             n_experts: int) -> int:
    """Per-(group, expert) slots: the configuration's capacity factor
    over the fair share, plus one, padded to the 8-row tile (to the 128
    tile from 1024 tokens up)."""
    c = int(capacity_factor * n_tokens * top_k // n_experts) + 1
    if n_tokens < 1024:
        return max(8, -(-c // 8) * 8)
    return -(-c // 128) * 128


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


def attention(dims: dict, w: dict, x, low: Optional[str]):
    """Causal self-attention of every row of x [B, T, d]."""
    b, t, d = x.shape
    h, kv = dims["n_heads"], dims["n_kv_heads"]
    dh = dims.get("head_dim") or d // h
    window = dims.get("swa_window")
    pos = jnp.arange(t)
    mask = pos[None, :] <= pos[:, None]
    if window is not None:
        mask &= pos[None, :] > pos[:, None] - window

    def one_row(xr):
        q = rope(matmul(xr, w["wq"], low).reshape(t, h, dh), pos,
                 dims["rope_theta"])
        k = rope(matmul(xr, w["wk"], low).reshape(t, kv, dh), pos,
                 dims["rope_theta"])
        v = matmul(xr, w["wv"], low).reshape(t, kv, dh)
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        q, k, v = lower(q, -1, low), lower(k, -1, low), lower(v, 0, low)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
            / np.sqrt(dh)
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        p = lower(p, -1, low)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        return matmul(o.reshape(t, h * dh), w["wo"], low)

    return jax.lax.map(one_row, x)


def route(dims: dict, router_w, x_flat, low: Optional[str]):
    """(gates [N, k], expert ids [N, k]) of a softmax top-k router."""
    probs = jax.nn.softmax(matmul(x_flat, router_w, low), axis=-1)
    gates, eids = jax.lax.top_k(probs, dims["top_k"])
    return gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9), eids


def keep_mask(eids, group, caps, n_experts: int):
    """Which (token, choice) assignments fit their expert's capacity.

    eids [N, k] with N tokens in batch-major order, group [N] the routing
    group of each token, caps [G] each group's capacity.  Within a group,
    an expert serves its assignments in token order; the ones beyond its
    capacity are dropped."""
    n = eids.shape[0]
    order = jnp.argsort(group, stable=True)
    e_sorted, g_sorted = eids[order], group[order]
    counts = jax.nn.one_hot(e_sorted, n_experts, dtype=jnp.int32).sum(1)
    before = jnp.cumsum(counts, axis=0) - counts
    first = jnp.searchsorted(g_sorted, g_sorted, side="left")
    pos = before - before[first]
    pos_k = jnp.take_along_axis(pos, e_sorted, axis=1)
    keep_sorted = pos_k < caps[g_sorted][:, None]
    return jnp.zeros((n, eids.shape[1]), bool).at[order].set(keep_sorted)


def experts(dims: dict, w: dict, x_flat, gates, eids, keep, bound: int,
            low: Optional[str]):
    """Sum over each token's kept choices of gate * SwiGLU expert."""
    n, d = x_flat.shape
    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, d), x_flat.dtype)])

    def one_expert(e, out):
        hit = (eids == e) & keep                           # [N, k]
        weight = jnp.sum(jnp.where(hit, gates, 0.0), axis=1)
        idx = jnp.nonzero(hit.any(1), size=bound, fill_value=n)[0]
        xs = x_pad[idx]
        hg = matmul(xs, w["w_gate"][e], low)
        hu = matmul(xs, w["w_up"][e], low)
        y = matmul(jax.nn.silu(hg) * hu, w["w_down"][e], low)
        wpad = jnp.concatenate([weight, jnp.zeros((1,), weight.dtype)])
        return out.at[idx].add(y * wpad[idx][:, None], mode="drop")

    return jax.lax.fori_loop(0, dims["num_experts"], one_expert,
                             jnp.zeros_like(x_flat))


def block(dims: dict, w: dict, x, group, caps, bound: int,
          low: Optional[str]):
    """One decoder block over x [B, T, d]."""
    b, t, d = x.shape
    x = x + attention(dims, w["attn"], rmsnorm(x, w["norm1"]["scale"]), low)
    h = rmsnorm(x, w["norm2"]["scale"]).reshape(b * t, d)
    gates, eids = route(dims, w["moe"]["router"], h, low)
    keep = keep_mask(eids, group, caps, dims["num_experts"])
    y = experts(dims, w["moe"], h, gates, eids, keep, bound, low)
    return x + y.reshape(b, t, d), jnp.sum(~keep)


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
    """The plain forward at one configuration and one traffic shape."""

    def __init__(self, dims: dict, layer_maker, *, low: Optional[str] = None):
        self.dims = dims
        self.low = low
        self._layer_maker = layer_maker
        self._blocks: Dict[int, object] = {}

    def _block_fn(self, bound: int):
        fn = self._blocks.get(bound)
        if fn is None:
            fn = self._blocks[bound] = jax.jit(functools.partial(
                block, self.dims, bound=bound, low=self.low))
        return fn

    def hidden(self, seed: int, jobs: Sequence[dict]):
        """Final hidden states of several forwards, layer by layer.

        Each job is ``{"device", "embed", "tokens" [B, T], "prompt_len",
        "n_shards"}``; jobs on different devices run side by side (the
        layer's weights are made on each job's device).  Returns one
        ``(hidden [B, T, d], dropped assignments)`` per job."""
        dims = self.dims
        state = []
        for job in jobs:
            bsz, seq = job["tokens"].shape
            group, sizes = routing_groups(bsz, seq, job["prompt_len"],
                                          job["n_shards"])
            caps = np.array([capacity(dims["capacity_factor"], int(n),
                                      dims["top_k"], dims["num_experts"])
                             for n in sizes], np.int32)
            bound = int(min(bsz * seq, caps.sum()))
            dev = job["device"]
            x = jnp.take(job["embed"], jax.device_put(job["tokens"], dev),
                         axis=0).astype(jnp.float32)
            state.append([x, jax.device_put(group, dev),
                          jax.device_put(caps, dev), bound, 0])
        for layer in range(dims["n_layers"]):
            for job, st in zip(jobs, state):
                w = self._layer_maker(seed, layer, device=job["device"])
                st[0], drops = self._block_fn(st[3])(w, st[0], st[1], st[2])
                st[4] = st[4] + drops
                del w
        return [(st[0], int(st[4])) for st in state]


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
