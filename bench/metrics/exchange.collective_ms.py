"""Device milliseconds per batch of the exchange's collectives
(collective-permute and all-to-all ops, their own time), averaged over
the cell's chips."""

from bench import trace_reduce


def read(run):
    if run.trace is None or not run.trace_devices or not run.batches:
        return None
    own = trace_reduce.op_self_seconds(
        run.trace, run.trace_devices, run.trace_lo, run.trace_hi,
        select=trace_reduce.is_collective)
    if not own:
        return None
    return 1e3 * sum(own.values()) / run.batches
