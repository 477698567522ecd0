"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, averaged over the
cell's chips, in percent."""

from bench import trace_reduce


def read(run):
    if run.trace is None or not run.trace_devices:
        return None
    window = (run.trace_hi - run.trace_lo) / 1e9
    if window <= 0:
        return None
    busy = trace_reduce.device_busy_s(run.trace, run.trace_devices,
                                      run.trace_lo, run.trace_hi)
    return 100.0 * (1.0 - busy / window)
