"""Cells at a size a CPU test run can hold: the registry's smoke widths of
``megatron-moe-32e`` (d_model 64, 4 experts top-2, vocab 256), scanned
like the served configurations, capacity factor 1 so that the Zipf
traffic overflows experts and the drop path runs.

The smoke model is served in float32, where the program and the
reference compute the same numbers and every served token is the
reference's best: the comparison then shows semantics and not rounding.
In bfloat16 a served token's gap at this size is set by which rows a
rounding-flipped route or capacity drop happens to touch (0 to 0.11 over
seeds and sampled batches), which no fixed limit separates from the
e4m3 control's (0.014 to 0.37)."""

from bench import cells

MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab": 256, "num_experts": 4,
         "top_k": 2, "capacity_factor": 1.0, "rope_theta": 1e6,
         "swa_window": None, "norm": "rmsnorm", "act": "silu",
         "tie_embeddings": False, "param_dtype": "float32",
         "compute_dtype": "float32"}

PLAN_STREAM = {"period_s": 0.2, "spread_s": 0.2, "clients": 4,
               "n_servers": 4, "m_gpus": 2, "tokens_per_gpu": 512,
               "bytes_per_token": 128, "top_k": 2, "expert_skew": 0.6,
               "repeat_p": 0.3, "drift_p": 0.05, "drift_low": 0.8,
               "drift_high": 1.2, "algorithm": "flash",
               "daemon_workers": 2, "check_sample": 16}

# Read at this size on the CPU (program in float32, e4m3 control): gap
# max 0.0 on every seed tried, control 0.014-0.37 over seeds 1..12.
# Plans: exact to 1e-15, a stale plan 0.02-0.1.
LIMITS = {"logit_gap_max": 1e-3, "plan_bytes_err": 1e-9,
          "plan_unanswered": 0}


def cell(kind: str, mesh: bool = False) -> cells.Cell:
    conf = {"name": "smoke", "registry": "megatron-moe-32e", "smoke": True,
            "model": dict(MODEL),
            "program": {"scan_layers": True, "capacity_factor": 1.0}}
    if kind == "prefill":
        traffic = {"name": "smoke_prefill", "batch": 4, "prompt_len": 32,
                   "gen_len": 1, "prompt_tokens": {"s": 1.0},
                   "plan_stream": None}
        correct = {"sample_batches": 4}
        like = "megatron32e.prefill.1c"
    else:
        traffic = {"name": "smoke_decode", "batch": 8, "prompt_len": 16,
                   "gen_len": 12, "prompt_tokens": {"s": 1.0},
                   "plan_stream": dict(PLAN_STREAM)}
        correct = {"sample_batches": 1, "sample_requests": 4}
        like = "mixtral8x7b.decode_plan.1c"
    correct["limits"] = dict(LIMITS)
    e2e, layer = cells.metrics_for(cells.load_benchmark(), like)
    return cells.Cell(
        name=f"smoke.{kind}", config=conf, traffic=traffic,
        chips=4 if mesh else 1,
        mesh={"shape": [2, 2, 1], "axes": ["pod", "data", "model"]}
        if mesh else None,
        a2a_impl="plan" if mesh else None,
        plan_cluster=[2, 2] if mesh else None, correct=correct,
        end_to_end=e2e, per_layer=layer)
