"""A decoder block of grouped-query attention and a top-k mixture of
SwiGLU experts (Megatron-MoE, Mixtral): the block the program's ``moe``
family builds, as the configuration's ``model`` sizes state it.

The block, as the configuration states it: RMSNorm, grouped-query
attention with rotary positions (halves rotated, causal, banded when a
window is set), RMSNorm, a top-k mixture of SwiGLU experts with a softmax
router whose k gates are renormalised.  Every layer is of this one kind,
scanned in one stack, ``blocks``.  The experts keep the capacity
semantics of an expert-parallel deployment: tokens are routed in groups
(one group per source shard and call), each expert takes at most
``capacity`` of a group's assignments, first come (in batch-major token
order) first served, and an assignment past the capacity contributes
nothing.

Everything ``bench/`` needs to know of the block is here, found by the
configuration's ``"arch": "moe_gqa"`` (``cells.load_arch``): the
parameter tree, the sizes compared with the program's configuration, the
plain forward of one layer, the routing and capacity rule, and the useful
FLOPs of a call.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.reference.model import HIGHEST, lower, matmul, rmsnorm, rope
from bench.weights import Leaf, stack


def _head_dim(dims: dict) -> int:
    return dims.get("head_dim") or dims["d_model"] // dims["n_heads"]


# -- the parameter tree ---------------------------------------------------------

def attention_leaves(dims: dict) -> Dict:
    d, h, kv = dims["d_model"], dims["n_heads"], dims["n_kv_heads"]
    dh, pdt = _head_dim(dims), dims["param_dtype"]
    return {"wq": Leaf((d, h * dh), pdt), "wk": Leaf((d, kv * dh), pdt),
            "wv": Leaf((d, kv * dh), pdt), "wo": Leaf((h * dh, d), pdt)}


def norm_leaves(dims: dict) -> Dict:
    return {"scale": Leaf((dims["d_model"],), "float32", init="ones")}


def global_leaves(dims: dict) -> Dict:
    """Embedding, final norm and LM head."""
    d, v, pdt = dims["d_model"], dims["vocab"], dims["param_dtype"]
    return {"embed": Leaf((v, d), pdt, init="embed"),
            "final_norm": norm_leaves(dims),
            "lm_head": Leaf((d, v), pdt, init="embed")}


def param_tree(dims: dict) -> Dict:
    d, f, e = dims["d_model"], dims["d_ff"], dims["num_experts"]
    pdt = dims["param_dtype"]
    experts = stack({"w_gate": Leaf((d, f), pdt), "w_up": Leaf((d, f), pdt),
                     "w_down": Leaf((f, d), pdt)}, e)
    block = {"norm1": norm_leaves(dims), "attn": attention_leaves(dims),
             "norm2": norm_leaves(dims),
             "moe": {"router": Leaf((d, e), "float32"), **experts}}
    return {**global_leaves(dims), "blocks": stack(block, dims["n_layers"])}


def layers(dims: dict) -> List[Tuple[str, int, str]]:
    """(stack, index in it, kind) of every layer, in order."""
    return [("blocks", i, "moe") for i in range(dims["n_layers"])]


def program_sizes(cfg) -> Dict:
    """The program's configuration in the keys of the file's ``model``."""
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab, "num_experts": cfg.moe.num_experts,
            "top_k": cfg.moe.top_k,
            "capacity_factor": cfg.moe.capacity_factor,
            "rope_theta": cfg.rope_theta, "swa_window": cfg.swa_window,
            "norm": cfg.norm, "act": cfg.act,
            "tie_embeddings": cfg.tie_embeddings,
            "param_dtype": cfg.param_dtype,
            "compute_dtype": cfg.compute_dtype}


def dispatch(dims: dict) -> Tuple[int, int, int]:
    """(experts a token goes to, experts of a layer, bytes a token sends)
    of the expert exchange, for the plan daemon's dispatch matrix."""
    return dims["top_k"], dims["num_experts"], dims["d_model"] * 2


# -- routing and capacity -------------------------------------------------------

def capacity(capacity_factor: float, n_tokens: int, top_k: int,
             n_experts: int) -> int:
    """Per-(group, expert) slots: the configuration's capacity factor
    over the fair share, plus one, padded to the 8-row tile (to the 128
    tile from 1024 tokens up)."""
    c = int(capacity_factor * n_tokens * top_k // n_experts) + 1
    if n_tokens < 1024:
        return max(8, -(-c // 8) * 8)
    return -(-c // 128) * 128


def routing(dims: dict, sizes: np.ndarray):
    """(arrays, static arguments) that every layer takes beside the routing
    groups, for groups of ``sizes`` tokens: each group's capacity, and the
    most rows any expert can take."""
    caps = np.array([capacity(dims["capacity_factor"], int(n),
                              dims["top_k"], dims["num_experts"])
                     for n in sizes], np.int32)
    return (caps,), {"bound": int(min(int(np.sum(sizes)), caps.sum()))}


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


# -- the plain forward of one layer ---------------------------------------------

def attention(dims: dict, w: dict, x, low: Optional[str]):
    """Causal self-attention of every row of x [B, T, d]."""
    b, t, d = x.shape
    h, kv = dims["n_heads"], dims["n_kv_heads"]
    dh = _head_dim(dims)
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
    """One decoder block over x [B, T, d]: (x, dropped assignments)."""
    b, t, d = x.shape
    x = x + attention(dims, w["attn"], rmsnorm(x, w["norm1"]["scale"]), low)
    h = rmsnorm(x, w["norm2"]["scale"]).reshape(b * t, d)
    gates, eids = route(dims, w["moe"]["router"], h, low)
    keep = keep_mask(eids, group, caps, dims["num_experts"])
    y = experts(dims, w["moe"], h, gates, eids, keep, bound, low)
    return x + y.reshape(b, t, d), jnp.sum(~keep)


KINDS = {"moe": block}


# -- useful FLOPs ----------------------------------------------------------------
#
# Useful work is what the model needs, not what the program executes:
# attention projections, causal scores at each query's actual context, the
# router, the top-k experts of every token (capacity padding and dropped
# tokens not counted separately: each token's k experts count once), and
# the LM head only where logits are produced (the last prompt position in
# prefill, every decode step).

def attention_proj_flops(dims: dict) -> int:
    d, h, kv = dims["d_model"], dims["n_heads"], dims["n_kv_heads"]
    dh = _head_dim(dims)
    return 2 * d * (h * dh + 2 * kv * dh) + 2 * h * dh * d


def layer_token_flops(dims: dict) -> int:
    """Per-token FLOPs of one MoE block without the attention scores."""
    router = 2 * dims["d_model"] * dims["num_experts"]
    experts_ = dims["top_k"] * 3 * 2 * dims["d_model"] * dims["d_ff"]
    return attention_proj_flops(dims) + router + experts_


def attention_score_flops(dims: dict, keys: int) -> int:
    """QK^T and PV for ``keys`` (query, key) pairs summed over queries."""
    return 2 * 2 * dims["n_heads"] * _head_dim(dims) * keys


def head_flops(dims: dict, n_positions: int) -> int:
    return 2 * dims["d_model"] * dims["vocab"] * n_positions


def prefill_flops(dims: dict, batch: int, prompt_len: int) -> int:
    """One prefill call: ``batch`` prompts of ``prompt_len`` tokens, logits
    at the last position only."""
    window = dims.get("swa_window")
    per_seq = (prompt_len * layer_token_flops(dims)
               + attention_score_flops(dims, flops.keys_seen(
                   0, prompt_len, window)))
    return batch * (dims["n_layers"] * per_seq + head_flops(dims, 1))


def decode_step_flops(dims: dict, batch: int, pos: int) -> int:
    """One decode step writing position ``pos`` for ``batch`` requests."""
    window = dims.get("swa_window")
    per_tok = (layer_token_flops(dims)
               + attention_score_flops(dims, flops.keys_seen(pos, 1,
                                                              window)))
    return batch * (dims["n_layers"] * per_tok + head_flops(dims, 1))
