"""Trace reduction on recorded v5e traces and on hand-made events."""

import os
import types

import pytest

from bench import flops, peaks, trace_reduce as tr
from bench import cells

DATA = os.path.join(os.path.dirname(__file__), "data")
V5E = peaks.peaks_for("TPU v5 lite")


def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


@pytest.fixture(scope="module")
def prefill_trace():
    # megatron-moe-32e, 2 layers, three 4 x 2048 prefill calls on one v5e
    return tr.load(os.path.join(DATA, "megatron_prefill.xplane.pb"))


@pytest.fixture(scope="module")
def kernel_trace():
    # a2a_pack then a2a_unpack of 2048 x 2048 bf16 blocks, three times
    return tr.load(os.path.join(DATA, "a2a_kernels.xplane.pb"))


def test_nesting_busy_and_self_time():
    outer = ev("%while.1 = (s32[]) while(s32[] %a)", 0, 100)
    inner = [ev("%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128] %x)", 10,
                30),
             ev("%fusion.2 = bf16[8,128]{1,0} fusion(bf16[8,128] %y)", 50,
                20)]
    later = ev("%copy.3 = s32[2]{0} copy(s32[2]{0} %z)", 150, 10)
    own = dict((e.name, t) for e, t in tr.self_times([outer, *inner, later]))
    assert own[outer.name] == 50 and own[later.name] == 10
    assert tr.busy_ns([outer, *inner, later]) == 110
    assert tr.idle_gaps([outer, *inner, later], 0, 200) == [(100, 150),
                                                             (160, 200)]
    assert tr.busy_ns(tr.clip([outer, later], 90, 155)) == 15


def test_idle_gaps_are_labelled_by_the_host_span_over_them():
    t = tr.Trace(device_ops={0: [ev("a = f32[1] add()", 0, 10),
                                 ev("b = f32[1] add()", 40, 10)]},
                 host_spans=[ev("bench.window", 0, 100),
                             ev("bench.token_fetch", 12, 20),
                             ev("bench.decode_step", 32, 6)])
    got = tr.labelled_idle(t, 0, 0, 100)
    assert got == pytest.approx({"bench.token_fetch": 30e-9,
                                 "none": 50e-9})
    assert tr.window_bounds(t) == (0, 100)


def test_hlo_text_parsing(kernel_trace):
    names = [e.name for e in kernel_trace.device_ops[0]]
    kernels = [n for n in names if tr.is_tpu_kernel(n)]
    assert len(kernels) == 6
    res, ops = tr.result_and_operands(kernels[1])
    assert res == [("bf16", (3072, 2048))]
    assert ops == [("s32", (2,)), ("bf16", (2048, 2048))]
    assert tr.opcode(kernels[0]) == "custom-call"
    assert tr.opcode("%t = (f32[2], s32[]) tuple(f32[2] %a, s32[] %b)") \
        == "tuple"
    assert tr.is_collective(
        "%collective-permute-start.1 = (bf16[4]) collective-permute-start("
        "bf16[4] %x)")
    assert not tr.is_collective(kernels[0])


def test_recorded_prefill_trace(prefill_trace):
    lo, hi = tr.window_bounds(prefill_trace)
    busy = tr.device_busy_s(prefill_trace, [0], lo, hi)
    assert 0 < busy <= (hi - lo) / 1e9
    own = tr.op_self_seconds(prefill_trace, [0], lo, hi)
    assert sum(own.values()) == pytest.approx(busy, rel=1e-9)
    bd = tr.breakdown(prefill_trace, [0], lo, hi)
    assert len(bd["device_ops"]) == 10
    # the grouped expert einsums lead: [32 experts, 1152 rows, 8192]
    assert "bf16[32,1152,8192]" in bd["device_ops"][0][0]


def _run(**kw):
    base = dict(trace=None, trace_devices=[0], trace_lo=0.0, trace_hi=0.0,
                peaks=V5E, window_s=1.0, chips=1, useful_flops=0.0,
                batches=1, telemetry=None, programs_in_window=0)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_a2a_roofline_of_the_recorded_kernels_is_a_share(kernel_trace):
    read = cells.load_reader("a2a_pack_roofline",
                             os.path.join(cells.BENCH_DIR, "metrics"))
    lo, hi = tr.window_bounds(kernel_trace)
    share = read(_run(trace=kernel_trace, trace_lo=lo, trace_hi=hi))
    # 16.8 MB read + written per call in ~68 us: about 30% of 819 GB/s
    assert 20 < share < 40


def test_a2a_roofline_cannot_pass_100_on_a_consistent_trace():
    read = cells.load_reader("a2a_pack_roofline",
                             os.path.join(cells.BENCH_DIR, "metrics"))
    name = ('%k = bf16[4096,2048]{1,0} custom-call(s32[2]{0} %i, '
            'bf16[6144,2048]{1,0} %x), custom_call_target="tpu_custom_call"')
    moved = flops.a2a_kernel_bytes(6144, 4096, 2048, 2)
    fastest_ns = moved / V5E["hbm_bytes_per_s"] * 1e9
    for dur in (fastest_ns, 2 * fastest_ns, 10 * fastest_ns):
        t = tr.Trace(device_ops={0: [ev(name, 0, dur)]}, host_spans=[])
        share = read(_run(trace=t, trace_lo=0, trace_hi=1e9))
        assert share <= 100.0 + 1e-9
    assert share == pytest.approx(10.0)
    t = tr.Trace(device_ops={0: [ev("%f = bf16[8]{0} fusion()", 0, 10)]},
                 host_spans=[])
    assert read(_run(trace=t, trace_lo=0, trace_hi=1e9)) is None


def test_mfu_cannot_pass_100_when_the_window_holds_the_work():
    read = cells.load_reader("model_step.mfu",
                             os.path.join(cells.BENCH_DIR, "metrics"))
    dims = cells.load_config("megatron-moe-32e-2l")["model"]
    work = flops.prefill_flops(dims, 4, 2048)
    fastest = work / V5E["bf16_flops_per_s"]
    assert read(_run(useful_flops=work, window_s=fastest)) \
        == pytest.approx(100.0)
    assert read(_run(useful_flops=work, window_s=4 * fastest)) \
        == pytest.approx(25.0)
    four = dict(dims, n_layers=12)
    w4 = flops.prefill_flops(four, 8, 2048)
    assert read(_run(useful_flops=w4, chips=4,
                     window_s=w4 / 4 / V5E["bf16_flops_per_s"])) \
        == pytest.approx(100.0)
    assert read(_run(useful_flops=0.0)) is None


def test_idle_share_and_collectives_from_a_trace():
    idle = cells.load_reader("device.idle_share",
                             os.path.join(cells.BENCH_DIR, "metrics"))
    coll = cells.load_reader("exchange.collective_ms",
                             os.path.join(cells.BENCH_DIR, "metrics"))
    ops = [ev("%all-to-all.1 = bf16[4,8]{1,0} all-to-all(bf16[4,8] %a)",
              0, 2e6),
           ev("%fusion.1 = bf16[8]{0} fusion(bf16[8] %b)", 3e6, 5e6),
           ev("%collective-permute.2 = bf16[8]{0} collective-permute("
              "bf16[8] %c)", 8e6, 1e6)]
    t = tr.Trace(device_ops={0: ops, 1: ops}, host_spans=[
        ev("bench.window", 0, 10e6)])
    run = _run(trace=t, trace_devices=[0, 1], trace_lo=0, trace_hi=10e6,
               batches=2)
    assert idle(run) == pytest.approx(20.0)
    assert coll(run) == pytest.approx(1.5)
