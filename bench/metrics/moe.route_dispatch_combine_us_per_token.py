"""Chip-microseconds of device time per token in the MoE layer's token
shuffling: the own time of the ops under the program's ``moe.route``,
``moe.dispatch`` and ``moe.combine`` scopes, over the tokens of the
traced window."""

from bench import program_trace


def read(run):
    return program_trace.scope_us_per_token(
        run, lambda s: (s.MOE_ROUTE, s.MOE_DISPATCH, s.MOE_COMBINE))
