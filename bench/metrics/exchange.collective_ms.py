"""Device milliseconds per batch of the expert exchange: the own time of
the ops under the program's ``exchange.pack``, ``exchange.stage`` and
``exchange.unpack`` scopes (the plan's slot packing, its all-to-all and
ppermute stages, and the unpacking; matched through the compiled HLO),
averaged over the cell's chips, over the batches of the window."""

from bench import program_trace


def read(run):
    own = program_trace.run_scope_seconds(run) if run.batches else None
    if own is None:
        return None
    s = program_trace.program_scopes()
    seconds = sum(own.get(name, 0.0) for name in (
        s.EXCHANGE_PACK, s.EXCHANGE_STAGE, s.EXCHANGE_UNPACK))
    if seconds <= 0:
        return None
    return 1e3 * seconds / run.batches
