"""Chip-microseconds of device time per token in the grouped expert FFN:
the own time of the ops under the program's ``moe.expert_ffn`` scope
(matched through the compiled HLO), over the tokens of the traced
window."""

from bench import program_trace


def read(run):
    return program_trace.scope_us_per_token(
        run, lambda s: (s.MOE_EXPERT_FFN,))
