"""Useful FLOPs of the served MoE model and bytes of the a2a kernels,
counted from the published shapes.

Useful work is what the model needs, not what the program executes:
attention projections, causal scores at each query's actual context, the
router, the top-k experts of every token (capacity padding and dropped
tokens not counted separately: each token's k experts count once), and
the LM head only where logits are produced (the last prompt position in
prefill, every decode step).  ``dims`` is the ``model`` dict of a
configuration file under ``bench/configs``.
"""

from __future__ import annotations

from typing import Optional


def _head_dim(dims: dict) -> int:
    return dims.get("head_dim") or dims["d_model"] // dims["n_heads"]


def _keys_seen(first_pos: int, n_queries: int,
               window: Optional[int]) -> int:
    """Sum over queries at positions first_pos .. first_pos+n-1 of the
    number of keys each attends to (causal, optionally banded)."""
    total = 0
    for p in range(first_pos, first_pos + n_queries):
        total += p + 1 if window is None else min(p + 1, window)
    return total


def layer_token_flops(dims: dict) -> int:
    """Per-token FLOPs of one MoE block without the attention scores."""
    d, h, kv = dims["d_model"], dims["n_heads"], dims["n_kv_heads"]
    dh = _head_dim(dims)
    proj = 2 * d * (h * dh + 2 * kv * dh) + 2 * h * dh * d
    router = 2 * d * dims["num_experts"]
    experts = dims["top_k"] * 3 * 2 * d * dims["d_ff"]
    return proj + router + experts


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
               + attention_score_flops(dims, _keys_seen(0, prompt_len,
                                                        window)))
    return batch * (dims["n_layers"] * per_seq + head_flops(dims, 1))


def decode_step_flops(dims: dict, batch: int, pos: int) -> int:
    """One decode step writing position ``pos`` for ``batch`` requests."""
    window = dims.get("swa_window")
    per_tok = (layer_token_flops(dims)
               + attention_score_flops(dims, _keys_seen(pos, 1, window)))
    return batch * (dims["n_layers"] * per_tok + head_flops(dims, 1))


def a2a_kernel_bytes(in_rows: int, out_rows: int, width: int,
                     itemsize: int) -> int:
    """HBM bytes one ``a2a_pack`` / ``a2a_unpack`` call must move.

    Pack gathers one block per output slot (every output row is read from
    the input once and written once); unpack scatters every input slot to
    one output block (every input row read once, written once; output
    blocks no slot names are never touched).  Either way the rows moved
    are the smaller of the two row counts, each read and written once.
    """
    return 2 * min(in_rows, out_rows) * width * itemsize
