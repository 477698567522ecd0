"""Share of the HBM roofline reached by the ``kernels/a2a_pack`` Pallas
kernels, the ops named ``a2a_pack`` and ``a2a_unpack``: the bytes each
call must move, from its shapes (``flops.py``), over the HBM peak, over
the calls' device time, in percent.  The kernels only copy, so bandwidth
bounds them."""

from bench import flops, trace_reduce

KERNELS = ("a2a_pack", "a2a_unpack")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "u8": 1, "s32": 4}


def _bytes(name):
    result, operands = trace_reduce.result_and_operands(name)
    data = [s for s in operands if len(s[1]) == 2]
    index = [s for s in operands if len(s[1]) == 1 and s[0] == "s32"]
    if len(result) != 1 or len(data) != 1 or len(index) != 1:
        return None
    (dt, (out_rows, width)), (_, (in_rows, in_width)) = result[0], data[0]
    if width != in_width or dt not in _ITEMSIZE:
        return None
    return flops.a2a_kernel_bytes(in_rows, out_rows, width, _ITEMSIZE[dt])


def read(run):
    bw = run.peaks.get("hbm_bytes_per_s")
    if run.trace is None or not bw:
        return None
    moved, seconds = 0.0, 0.0
    for dev in run.trace_devices:
        for ev in run.trace.device_ops.get(dev, ()):
            if not (run.trace_lo <= ev.start_ns < run.trace_hi
                    and trace_reduce.instruction(ev.name).rsplit(".", 1)[0]
                    in KERNELS):
                continue
            b = _bytes(ev.name)
            if b is None:
                continue
            moved += b
            seconds += ev.dur_ns / 1e9
    if seconds <= 0:
        return None
    return 100.0 * moved / bw / seconds
