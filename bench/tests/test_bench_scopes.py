"""Readers of the program's own names: device time by ``jax.named_scope``
(through the compiled HLO), idle time by the window thread's spans and by
the plan daemon's, and step dispatch spans."""

import contextlib
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, harness, peaks, program_trace as pt
from bench import trace_reduce as tr
from bench.tests import smoke
from repro import scopes

DATA = os.path.join(os.path.dirname(__file__), "data")
METRICS = os.path.join(cells.BENCH_DIR, "metrics")
SCOPE_READERS = ("moe.expert_ffn_us_per_token",
                 "moe.route_dispatch_combine_us_per_token",
                 "attention.kv_cache_us_per_token")
W, C, D = "host#0:python", "host#1:python", "host#2:python"


def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


def sp(name, start, dur, thread):
    return pt.Span(name, float(start), float(dur), thread)


def read(name, run):
    return cells.load_reader(name, METRICS)(run)


def _run(trace, cell="hand-made", **kw):
    lo, hi = tr.window_bounds(trace)
    base = dict(trace=trace, trace_devices=[0], trace_lo=lo, trace_hi=hi,
                tokens=0, cell=types.SimpleNamespace(name=cell))
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_program_span_prefixes_hold_every_span_and_no_scope():
    kept = pt.PROGRAM_SPAN_PREFIXES
    assert all(n.startswith(kept) for n in scopes.HOST_SPANS)
    assert not any(n.startswith(kept + (tr.HOST_SPAN_PREFIX,))
                   for n in scopes.DEVICE_SCOPES)


# A profile's op event, and the same instruction's line in compiled HLO
EVENT = ("%fusion.84 = s32[1,16,4,128]{3,2,1,0:T(4,128)S(1)} fusion(s32[4,"
         "2048]{1,0:T(4,128)} %batch__tokens__.1), kind=kLoop, "
         "calls=%fused_computation.117")


@pytest.mark.parametrize("line", [
    "  " + EVENT + ', metadata={op_name="jit(f)/moe.route/add"}',
    "  ROOT " + EVENT.replace("%", "") + ", backend_config={}",
])
def test_an_event_and_its_compiled_line_have_one_signature(line):
    head = "fusion.84 s32[1,16,4,128]{3,2,1,0:T(4,128)S(1)}"
    assert pt.signatures(EVENT) == pt.signatures(line) == (
        head, head + " fusion(s32[4,2048]{1,0:T(4,128)} batch__tokens__.1)")


def test_signatures_of_tuples_comments_and_other_text():
    text = ("%t.2 = (bf16[8]{0:T(256)}, s32[]) fusion(bf16[8]{0} %a, "
            "/*index=1*/s32[] %b), kind=kLoop")
    assert pt.signatures(text) == (
        "t.2 (bf16[8]{0:T(256)}, s32[])",
        "t.2 (bf16[8]{0:T(256)}, s32[]) fusion(bf16[8]{0} a, s32[] b)")
    assert pt.signatures("%p.1 = f32[2]{0} parameter(0)")[1] \
        == "p.1 f32[2]{0} parameter(0)"
    assert pt.signatures("HloModule jit_f, is_scheduled=true") is None
    assert pt.signatures("ENTRY %main.9 (p: bf16[8]) -> bf16[8] {") is None


PREFILL = '''HloModule jit_prefill_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[8]) -> bf16[8] {
  ROOT %dot.1 = bf16[8]{0} dot(bf16[8]{0} %param_0), metadata={op_name="jit(prefill_step)/attention.kv_cache/while/body/transformer.block/moe.expert_ffn/dot_general"}
}

ENTRY %main.9 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  %fusion.190 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(prefill_step)/attention.kv_cache/while/body/transformer.block/moe.expert_ffn/dot_general"}
  %copy_dynamic-update-slice_fusion.5 = bf16[2,8]{1,0} fusion(bf16[8]{0} %fusion.190), kind=kLoop, metadata={op_name="jit(prefill_step)/attention.kv_cache/while/body/dynamic_update_slice"}
  %fusion.197 = s32[8]{0} fusion(bf16[8]{0} %p), kind=kLoop, metadata={op_name="jit(prefill_step)/attention.kv_cache/while/body/transformer.block/moe.dispatch/cumsum"}
  %add.3 = bf16[8]{0} add(bf16[8]{0} %p, bf16[8]{0} %p), metadata={op_name="jit(prefill_step)/attention.kv_cache/while/body/transformer.block/add"}
  ROOT %gather.2 = bf16[8]{0} gather(bf16[8]{0} %p), metadata={op_name="jit(prefill_step)/jit(_take)/gather"}
}
'''

# another module: fusion.190 again, with another scope and other operands
STEP = '''HloModule jit_serve_step, is_scheduled=true

ENTRY %main.3 (q: bf16[8]) -> bf16[8] {
  ROOT %fusion.190 = bf16[8]{0} fusion(bf16[8]{0} %q), kind=kLoop, metadata={op_name="jit(serve_step)/transformer.block/moe.combine/mul"}
}
'''


def test_hlo_scopes_take_the_innermost_scope_of_each_instruction():
    table = pt.hlo_scopes([PREFILL, STEP], scopes.DEVICE_SCOPES)
    assert table["dot.1 bf16[8]{0}"] == scopes.MOE_EXPERT_FFN
    assert table["fusion.190 bf16[8]{0} fusion(bf16[8]{0} p)"] \
        == scopes.MOE_EXPERT_FFN
    assert table["fusion.190 bf16[8]{0} fusion(bf16[8]{0} q)"] \
        == scopes.MOE_COMBINE
    # the two modules' fusion.190 share a head with different scopes
    assert table["fusion.190 bf16[8]{0}"] is None
    assert table["copy_dynamic-update-slice_fusion.5 bf16[2,8]{1,0}"] \
        == scopes.ATTENTION_KV_CACHE
    assert table["fusion.197 s32[8]{0}"] == scopes.MOE_DISPATCH
    assert table["add.3 bf16[8]{0}"] == scopes.TRANSFORMER_BLOCK
    assert table["gather.2 bf16[8]{0}"] is None      # no scope of ours
    assert table["p bf16[8]{0}"] is None              # no op_name


def _scoped_trace():
    """Two prefill runs and a serve step on one device, 100 tokens."""
    ops = []
    for t0 in (0, 1000):
        ops += [ev("%fusion.190 = bf16[8]{0} fusion(bf16[8]{0} %p), "
                   "kind=kOutput", t0 + 10, 300),
                ev("%fusion.197 = s32[8]{0} fusion(bf16[8]{0} %other)",
                   t0 + 320, 40),
                ev("%copy_dynamic-update-slice_fusion.5 = bf16[2,8]{1,0} "
                   "fusion(bf16[8]{0} %fusion.190)", t0 + 360, 50),
                ev("%add.3 = bf16[8]{0} add(bf16[8]{0} %p, bf16[8]{0} %p)",
                   t0 + 410, 20),
                ev("%gather.2 = bf16[8]{0} gather(bf16[8]{0} %p)", t0 + 430,
                   10)]
    ops.append(ev("%fusion.190 = bf16[8]{0} fusion(bf16[8]{0} %q)", 1500,
                  5))
    # an instruction no compiled module holds
    ops.append(ev("%fusion.7 = f32[4]{0} fusion(f32[4]{0} %r)", 1600, 5))
    return tr.Trace(device_ops={0: ops},
                    host_spans=[ev("bench.window", 0, 2000)])


def test_scope_readers_read_own_device_time_per_token(monkeypatch):
    t = _scoped_trace()
    table = pt.hlo_scopes([PREFILL, STEP], scopes.DEVICE_SCOPES)
    own = pt.scope_seconds(t, [0], 0, 2000, table)
    # fusion.197's operands differ from the module's: found by its head
    assert pt.by_scope(own) == pytest.approx({
        scopes.MOE_EXPERT_FFN: 600e-9, scopes.MOE_DISPATCH: 80e-9,
        scopes.ATTENTION_KV_CACHE: 100e-9, scopes.MOE_COMBINE: 5e-9,
        scopes.TRANSFORMER_BLOCK: 40e-9, None: 25e-9})
    assert {op: s for (scope, op), s in own.items() if scope is None} \
        == pytest.approx({"gather.2 gather bf16[8]": 20e-9,
                          "fusion.7 fusion f32[4]": 5e-9})
    monkeypatch.setitem(pt._TABLES, "hand-made", table)
    run = _run(t, tokens=100)
    # chip-microseconds per token
    assert read("moe.expert_ffn_us_per_token", run) == pytest.approx(6e-3)
    assert read("moe.route_dispatch_combine_us_per_token", run) \
        == pytest.approx(0.85e-3)
    assert read("attention.kv_cache_us_per_token", run) \
        == pytest.approx(1e-3)


def test_scope_readers_read_nothing_without_scopes(monkeypatch):
    monkeypatch.setitem(pt._TABLES, "hand-made", {})
    for name in SCOPE_READERS:
        assert read(name, _run(_scoped_trace(), tokens=100)) is None
        bare = _run(_scoped_trace(), tokens=100)
        bare.trace = None
        assert read(name, bare) is None
    # a program that names no scopes (the parent of this reader) is not
    # even compiled
    monkeypatch.setattr(pt, "program_scopes", lambda: None)
    monkeypatch.setattr(pt, "cell_scope_table", None)
    for name in SCOPE_READERS:
        assert read(name, _run(_scoped_trace(), tokens=100)) is None


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_the_smoke_cells_programs_name_their_ops(kind, monkeypatch):
    monkeypatch.setattr(pt, "_TABLES", {})
    cell = smoke.cell(kind)
    table = pt.cell_scope_table(cell)
    found = set(table.values())
    assert {scopes.MOE_ROUTE, scopes.MOE_DISPATCH, scopes.MOE_EXPERT_FFN,
            scopes.MOE_COMBINE, scopes.ATTENTION_KV_CACHE} <= found
    assert pt.cell_scope_table(cell) is table        # made once
    assert pt.cell_programs(smoke.cell(kind, mesh=True)) == []


# PREFILL as a tree without scopes compiled it: no op_name of ours, and
# instruction names numbered otherwise
RAN = (PREFILL.replace("fusion.190", "fusion.193")
       .replace("add.3", "add.4")
       .replace("attention.kv_cache/", "").replace("transformer.block/", "")
       .replace("moe.expert_ffn/", "").replace("moe.dispatch/", ""))


def test_the_programs_that_ran_take_the_scopes_of_the_same_lines():
    table = pt.aligned_scopes([RAN], [PREFILL], scopes.DEVICE_SCOPES)
    assert table["fusion.193 bf16[8]{0} fusion(bf16[8]{0} p)"] \
        == scopes.MOE_EXPERT_FFN
    assert table["add.4 bf16[8]{0}"] == scopes.TRANSFORMER_BLOCK
    assert table["copy_dynamic-update-slice_fusion.5 bf16[2,8]{1,0}"] \
        == scopes.ATTENTION_KV_CACHE
    assert "fusion.190 bf16[8]{0}" not in table
    # programs that differ in more than names and metadata do not align
    other = PREFILL.replace("s32[8]{0} fusion", "s32[4]{0} fusion")
    assert pt.aligned_scopes([RAN], [other], scopes.DEVICE_SCOPES) is None
    assert pt.aligned_scopes([RAN], [STEP], scopes.DEVICE_SCOPES) is None
    # other lines around the instructions do not matter
    assert pt.aligned_scopes([RAN + "\nFileNames\n1 \"a.py\"\n"], [PREFILL],
                             scopes.DEVICE_SCOPES) == table


def test_programs_compiled_without_scopes_align_with_the_scoped(
        monkeypatch):
    cell = smoke.cell("decode")
    scoped = pt.cell_programs(cell)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    ran = pt.cell_programs(cell)
    assert not any(pt.hlo_scopes(ran, scopes.DEVICE_SCOPES).values())
    table = pt.aligned_scopes(ran, scoped, scopes.DEVICE_SCOPES)
    assert {scopes.MOE_EXPERT_FFN, scopes.MOE_DISPATCH,
            scopes.ATTENTION_KV_CACHE} <= set(table.values())
    # every instruction of the programs that ran is found
    for line in "\n".join(ran).splitlines():
        keys = pt.signatures(line)
        if keys is not None:
            assert keys[1] in table


def test_the_reader_lowers_the_programs_the_harness_runs():
    from bench import weights
    from repro.launch import serve

    cell = smoke.cell("decode")
    traffic, dims = cell.traffic, cell.config["model"]
    cfg = harness.model_config(cell)
    b, s = traffic["batch"], traffic["prompt_len"]
    dev = jax.devices()[0]
    prefill = serve.make_prefill_step(
        cfg, None, cache_len=s + traffic["gen_len"])
    step = serve.make_serve_step(cfg, None)
    params = weights.make_params(dims, 5, device=dev)
    batch = {"tokens": jax.device_put(np.zeros((b, s), np.int32), dev)}
    logits, cache = prefill(params, batch)
    toks = jnp.argmax(logits, -1)
    want = [prefill.lower(params, batch).as_text(),
            step.lower(params, cache, toks, jnp.int32(s)).as_text()]
    assert [lo.as_text() for lo in pt.cell_lowered(cell)] == want


def test_a_cache_of_unscoped_programs_is_compiled_past(monkeypatch):
    flag = "jax_compilation_cache_include_metadata_in_key"
    calls = []

    def programs(cell):
        calls.append(getattr(jax.config, flag))
        return [RAN] if len(calls) == 1 else [PREFILL]

    monkeypatch.setattr(pt, "_TABLES", {})
    monkeypatch.setattr(pt, "cell_programs", programs)
    table = pt.cell_scope_table(types.SimpleNamespace(name="c"))
    assert calls == [False, True] and not getattr(jax.config, flag)
    # keyed by the instructions of the programs that ran
    assert table["fusion.193 bf16[8]{0}"] == scopes.MOE_EXPERT_FFN


def _two_thread_trace():
    """Window thread W dispatches a step at 10-20 and fetches at 40-60; a
    client thread C waits on a plan for the whole window, while the
    daemon's worker D synthesizes at 30-70.  The device idles 10-30 and
    50-80."""
    ops = {0: [ev("a = f32[1] add()", 0, 10), ev("b = f32[1] add()", 30, 20),
               ev("c = f32[1] add()", 80, 20)]}
    host = [ev("bench.window", 0, 100), ev("bench.plan_request", 0, 100),
            ev("bench.decode_step", 10, 10), ev("bench.token_fetch", 40, 20)]
    spans = [sp("bench.window", 0, 100, W),
             sp("bench.plan_request", 0, 100, C),
             sp("bench.decode_step", 10, 10, W),
             sp(scopes.SERVE_DECODE_STEP, 12, 6, W),
             sp(scopes.PLAN_SUBMIT, 1, 1, C),
             sp(scopes.PLAN_SYNTHESIZE, 30, 40, D),
             sp("bench.token_fetch", 40, 20, W)]
    return tr.Trace(device_ops=ops, host_spans=host), spans


def test_idle_goes_to_the_innermost_span_of_the_window_thread():
    t, spans = _two_thread_trace()
    assert pt.window_thread(spans) == W
    got = pt.thread_idle(t, spans, 0, 0, 100)
    assert got == pytest.approx({"bench.decode_step": 4e-9,
                                 scopes.SERVE_DECODE_STEP: 6e-9,
                                 "none": 30e-9, "bench.token_fetch": 10e-9})
    # the ledger's attribution hands the whole first gap, and the second,
    # to the client's long wait, which the window thread never did
    old = tr.labelled_idle(t, 0, 0, 100)
    assert old["bench.plan_request"] == pytest.approx(50e-9)
    assert "bench.plan_request" not in got


def test_innermost_pieces_of_nested_spans():
    spans = [sp("a", 0, 100, W), sp("b", 10, 30, W), sp("c", 20, 5, W),
             sp("d", 60, 10, W), sp("e", 200, 10, W)]
    assert pt.innermost(spans) == [(0, 10, "a"), (10, 20, "b"),
                                   (20, 25, "c"), (25, 40, "b"),
                                   (40, 60, "a"), (60, 70, "d"),
                                   (70, 100, "a"), (200, 210, "e")]


def test_idle_inside_and_outside_the_daemons_spans():
    t, spans = _two_thread_trace()
    d = pt.idle_within(t, spans, [0], 0, 100,
                       (scopes.PLAN_SUBMIT, scopes.PLAN_SYNTHESIZE))
    # union 1-2 and 30-70: 41 ns; idle inside it 50-70 = 20 ns
    assert d == pytest.approx({"window_s": 100e-9, "spans_s": 41e-9,
                               "idle_in_s": 20e-9, "idle_out_s": 30e-9})
    daemon = pt.report(t, spans, [0])["daemon"]
    assert daemon["idle_share_in"] == pytest.approx(20 / 41)
    assert daemon["idle_share_out"] == pytest.approx(30 / 59)


def test_dispatch_mean_reads_the_program_step_spans_in_the_window():
    _, spans = _two_thread_trace()
    spans += [sp(scopes.SERVE_PREFILL, 50, 2e6, W),
              sp(scopes.SERVE_DECODE_STEP, 150, 1e6, W)]
    # spans starting in [0, 100): 6 ns and 2 ms
    assert pt.dispatch_ms_mean(spans, 0, 100) \
        == pytest.approx((6 + 2e6) / 2 / 1e6)
    assert pt.dispatch_ms_mean([], 0, 100) is None


# Readings of the recorded traces by the readers and ``breakdown`` of the
# benchmark as it was when they were recorded.
RECORDED = {
    "megatron_prefill.xplane.pb": {
        "window": (50114511.0, 259479175.0), "busy_s": 0.205131684,
        "device.idle_share": 2.021821600229534, "a2a_pack_roofline": None,
        "top": ("fusion.190 fusion bf16[32,1152,8192]", 0.046531133),
        "idle_gaps": [["none", 0.004232979999999999]]},
    "a2a_kernels.xplane.pb": {
        "window": (43933115.0, 47941212.0), "busy_s": 0.000409998,
        "device.idle_share": 89.77075654606163,
        "a2a_pack_roofline": 30.09660181593529,
        "top": ("_unknown_.1 custom-call bf16[2048,2048]",
                0.00020430600000000002),
        "idle_gaps": [["none", 0.003598099]]},
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_traces_read_as_before(name, capsys):
    want = RECORDED[name]
    path = os.path.join(DATA, name)
    t = tr.load(path)
    lo, hi = tr.window_bounds(t)
    assert (lo, hi) == want["window"]
    assert tr.device_busy_s(t, [0], lo, hi) == pytest.approx(
        want["busy_s"], rel=1e-12)
    bd = tr.breakdown(t, [0], lo, hi)
    assert bd["device_ops"][0] == [want["top"][0], pytest.approx(
        want["top"][1], rel=1e-12)]
    assert bd["idle_gaps"] == [[k, pytest.approx(v, rel=1e-12)]
                               for k, v in want["idle_gaps"]]
    run = _run(t, trace_lo=lo, trace_hi=hi, batches=3,
               peaks=peaks.peaks_for("TPU v5 lite"))
    for reader in ("device.idle_share", "a2a_pack_roofline"):
        got = read(reader, run)
        assert got == (None if want[reader] is None
                       else pytest.approx(want[reader], rel=1e-12))
    # recorded outside the harness: no spans, so every gap reads "none"
    spans = pt.load_spans(path)
    assert spans == [] and pt.window_thread(spans) is None
    assert pt.main([path]) == 0
    out = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert out["dispatch_ms_mean"] is None
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert out["idle_by_window_thread_s"] == [["none", pytest.approx(
        (hi - lo) / 1e9 - want["busy_s"], rel=1e-9)]]


def test_a_traced_run_keeps_the_program_spans_on_their_threads(tmp_path):
    kept = str(tmp_path / "trace")
    out = harness.run(smoke.cell("decode"), 2 ** 33 + 11, 1.5, True,
                      jax.devices(), keep_trace=kept)
    line = out["line"]
    assert line["correct"], line["checks"]
    assert line["metrics"]["lowering.compiles_in_window"]["value"] == 0
    spans = pt.load_spans(kept)
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    window = pt.window_thread(spans)
    for name in (scopes.SERVE_PREFILL, scopes.SERVE_DECODE_STEP,
                 scopes.SERVE_ARGMAX):
        assert {s.thread for s in by[name]} == {window}
    assert {s.stats.get("outcome") for s in by[scopes.PLAN_SUBMIT]} \
        <= {"hit", "queued"}
    synth = by[scopes.PLAN_SYNTHESIZE]
    assert window not in {s.thread for s in synth}
    assert {s.stats["kind"] for s in synth} <= {
        "cold", "warm", "upgrade", "prewarm", "rerepair"}
