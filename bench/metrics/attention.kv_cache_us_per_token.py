"""Chip-microseconds of device time per token spent moving the KV cache:
the own time of the ops under the program's ``attention.kv_cache`` scope
(the per-step cache write, and the slicing of each layer's cache out of
the stacked cache and back around the layer scan), over the tokens of
the traced window."""

from bench import program_trace


def read(run):
    return program_trace.scope_us_per_token(
        run, lambda s: (s.ATTENTION_KV_CACHE,))
