"""A dense decoder block, grouped-query attention and a SwiGLU MLP: the
program's ``dense`` family.  A test fixture and no benchmark
configuration: ``test_bench_arch.py`` drops it into a copy of ``bench/``
as ``bench/arch/dense_gqa.py``, beside a configuration and a cell that
name it, to show that an architecture is added by new files alone."""

import jax
import jax.numpy as jnp

from bench import flops
from bench.arch import moe_gqa
from bench.reference.model import matmul, rmsnorm
from bench.weights import Leaf, stack


def param_tree(dims):
    d, f, pdt = dims["d_model"], dims["d_ff"], dims["param_dtype"]
    block = {"norm1": moe_gqa.norm_leaves(dims),
             "attn": moe_gqa.attention_leaves(dims),
             "norm2": moe_gqa.norm_leaves(dims),
             "mlp": {"w_gate": Leaf((d, f), pdt), "w_up": Leaf((d, f), pdt),
                     "w_down": Leaf((f, d), pdt)}}
    top = moe_gqa.global_leaves(dims)
    if dims["tie_embeddings"]:
        del top["lm_head"]
    return {**top, "blocks": stack(block, dims["n_layers"])}


def layers(dims):
    return [("blocks", i, "dense") for i in range(dims["n_layers"])]


def program_sizes(cfg):
    return {"family": cfg.family, "n_layers": cfg.n_layers,
            "d_model": cfg.d_model, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.resolved_head_dim,
            "d_ff": cfg.d_ff, "vocab": cfg.vocab,
            "rope_theta": cfg.rope_theta, "swa_window": cfg.swa_window,
            "norm": cfg.norm, "act": cfg.act,
            "tie_embeddings": cfg.tie_embeddings,
            "param_dtype": cfg.param_dtype,
            "compute_dtype": cfg.compute_dtype}


def routing(dims, sizes):
    return (), {}


def block(dims, w, x, group, low):
    x = x + moe_gqa.attention(dims, w["attn"],
                              rmsnorm(x, w["norm1"]["scale"]), low)
    h, m = rmsnorm(x, w["norm2"]["scale"]), w["mlp"]
    y = matmul(jax.nn.silu(matmul(h, m["w_gate"], low))
               * matmul(h, m["w_up"], low), m["w_down"], low)
    return x + y, jnp.int32(0)


KINDS = {"dense": block}


def _token_flops(dims):
    return (moe_gqa.attention_proj_flops(dims)
            + 3 * 2 * dims["d_model"] * dims["d_ff"])


def prefill_flops(dims, batch, prompt_len):
    keys = flops.keys_seen(0, prompt_len, dims["swa_window"])
    per_seq = (prompt_len * _token_flops(dims)
               + moe_gqa.attention_score_flops(dims, keys))
    return batch * (dims["n_layers"] * per_seq
                    + moe_gqa.head_flops(dims, 1))


def decode_step_flops(dims, batch, pos):
    keys = flops.keys_seen(pos, 1, dims["swa_window"])
    per_tok = _token_flops(dims) + moe_gqa.attention_score_flops(dims, keys)
    return batch * (dims["n_layers"] * per_tok + moe_gqa.head_flops(dims, 1))
