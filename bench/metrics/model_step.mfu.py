"""Useful model FLOPs of the calls completed in the window (``flops.py``:
attention projections, causal scores at the actual context, router, the
top-k experts, LM head where logits are produced) over window seconds x
chips x the chip's bf16 peak, in percent."""


def read(run):
    peak = run.peaks.get("bf16_flops_per_s")
    if not peak or run.window_s <= 0 or not run.useful_flops:
        return None
    return 100.0 * run.useful_flops / (run.window_s * run.chips * peak)
