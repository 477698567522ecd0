#!/usr/bin/env python3
"""The program's own names in a profile: device time by its scopes, host
time by its spans on each thread.

Device scopes.  The program traces its work under ``jax.named_scope``
names (``repro.scopes``), which land in the ``op_name`` metadata of the
compiled HLO.  A profile's op events carry only the instruction's text
(``%fusion.3 = bf16[...] fusion(...)``), so an op's scope is read from
the compiled HLO of the cell's programs: ``cell_scope_table`` lowers and
compiles the prefill and decode steps as the harness builds them (after
the window; the persistent compile cache holds them by then) and maps
each instruction, by its text, to the innermost scope on its ``op_name``.

Host spans.  The program opens ``serve.*`` and ``plan.*`` spans
(``jax.profiler.TraceAnnotation``) on the thread that does the work.
``load_spans`` reads them, with the benchmark's ``bench.*`` spans, each
with its thread and its stats, from a trace kept by ``run.py
--keep-trace``.  ``thread_idle`` gives each device idle gap to what the
thread that runs the window was doing; ``idle_within`` splits idle time
inside and outside the plan daemon's spans, on any thread.

As a script, on a kept trace::

    python3 bench/program_trace.py <trace dir> [--workload <cell>]

prints one JSON object: idle by the window thread's spans, idle inside
and outside the daemon's spans, the mean step dispatch and, with
``--workload`` (which compiles the cell's programs where it runs), device
seconds by scope.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import trace_reduce  # noqa: E402

PROGRAM_SPAN_PREFIXES = ("serve.", "plan.")


def program_scopes():
    """The program's ``repro.scopes`` (its scope and span names), or None
    for a program that names none."""
    try:
        from repro import scopes
    except ImportError:
        return None
    return scopes


# -- an op's scope, from the compiled HLO ---------------------------------------

_COMMENT = re.compile(r"/\*.*?\*/")
_HEAD = re.compile(r"\s*(?:ROOT\s+)?([\w.\-]+)\s*=\s*")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def _group_end(text: str, i: int) -> int:
    """Index just past the parenthesised group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def signatures(text: str) -> Optional[Tuple[str, Optional[str]]]:
    """(``name type``, ``name type opcode(operands)``) of one HLO
    instruction's text, without ``%``, comments or the attributes after
    the operands: a profile's op event and the compiled module's line of
    the same instruction give the same pair."""
    text = _COMMENT.sub("", text).replace("%", "")
    m = _HEAD.match(text)
    if not m:
        return None
    i = m.end()
    j = _group_end(text, i) if text.startswith("(", i) else text.find(" ", i)
    if j < 0:
        return None
    head = f"{m.group(1)} {text[i:j]}"
    o = _OPCODE.match(text, j)
    if not o:
        return head, None
    return head, f"{head} {text[o.start(1):_group_end(text, o.end() - 1)]}"


def scope_of(op_name: str, names: Iterable[str]) -> Optional[str]:
    """The innermost of ``names`` on an ``op_name`` path, else None."""
    for part in reversed(op_name.split("/")):
        if part in names:
            return part
    return None


def _table(pairs: Iterable[Tuple[str, str]], names: Iterable[str]
           ) -> Dict[str, Optional[str]]:
    """{signature: scope or None}: each (line, scoped line) pair files
    both ``signatures`` of the line under the innermost of ``names`` on
    the scoped line's ``op_name``.  A signature filed under two scopes
    maps to None."""
    names = frozenset(names)
    seen: Dict[str, set] = defaultdict(set)
    for line, scoped in pairs:
        keys = signatures(line)
        if keys is None:
            continue
        m = _OP_NAME.search(scoped)
        scope = scope_of(m.group(1), names) if m else None
        for key in keys:
            if key is not None:
                seen[key].add(scope)
    return {k: (next(iter(s)) if len(s) == 1 else None)
            for k, s in seen.items()}


def hlo_scopes(texts: Iterable[str], names: Iterable[str]
               ) -> Dict[str, Optional[str]]:
    """``_table`` of the instructions of compiled HLO modules."""
    return _table(((line, line) for text in texts
                   for line in text.splitlines()), names)


def aligned_scopes(ran: Sequence[str], scoped: Sequence[str],
                   names: Iterable[str]) -> Optional[Dict[str, Optional[str]]]:
    """``_table`` of the modules ``ran``, each instruction under the scope
    of the instruction in the same place of ``scoped``: the same programs
    compiled with other metadata, whose instruction names may be numbered
    otherwise (and whose text may hold other lines besides).  None where
    the instructions differ in number, result type or opcode."""
    pairs = []
    for a, b in zip(ran, scoped, strict=True):
        ins_a = [(line, signatures(line)) for line in a.splitlines()]
        ins_b = [(line, signatures(line)) for line in b.splitlines()]
        ins_a = [i for i in ins_a if i[1] is not None]
        ins_b = [i for i in ins_b if i[1] is not None]
        if len(ins_a) != len(ins_b) or any(
                _shape_op(ka) != _shape_op(kb)
                for (_, ka), (_, kb) in zip(ins_a, ins_b)):
            return None
        pairs += [(la, lb) for (la, _), (lb, _) in zip(ins_a, ins_b)]
    return _table(pairs, names)


def _shape_op(keys: Tuple[str, Optional[str]]) -> Tuple[str, str]:
    """(result type, opcode) of an instruction's ``signatures``."""
    head, full = keys
    kind = head.split(" ", 1)[1]
    return kind, (full[len(head) + 1:].split("(", 1)[0] if full else "")


def scope_seconds(trace: trace_reduce.Trace, devices: Sequence[int],
                  lo: float, hi: float, table: Dict[str, Optional[str]]
                  ) -> Dict[Tuple[Optional[str], str], float]:
    """Own seconds of the ops in [lo, hi) by (scope or None, op's short
    name), averaged over ``devices``.  An op's scope is its full
    signature's in ``table``, else its ``name type``'s."""
    scope: Dict[str, Optional[str]] = {}
    total: Dict[Tuple[Optional[str], str], float] = defaultdict(float)
    for d in devices:
        ops = trace_reduce.clip(trace.device_ops.get(d, ()), lo, hi)
        for ev, own in trace_reduce.self_times(ops):
            if ev.name not in scope:
                head, full = signatures(ev.name) or (None, None)
                scope[ev.name] = table.get(full, table.get(head))
            key = (scope[ev.name], trace_reduce.short_name(ev.name))
            total[key] += own / 1e9 / len(devices)
    return dict(total)


def by_scope(seconds: Dict[Tuple[Optional[str], str], float]
             ) -> Dict[Optional[str], float]:
    out: Dict[Optional[str], float] = defaultdict(float)
    for (scope, _), s in seconds.items():
        out[scope] += s
    return dict(out)


def cell_lowered(cell) -> list:
    """The cell's prefill step and, where it decodes, its decode step,
    lowered as the harness runs them: arguments committed to the first
    device (the position excepted), since that changes the lowered module
    and so the persistent cache's key.  None for a cell on a mesh."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from repro.launch import serve

    from bench import harness, weights

    if cell.mesh or cell.a2a_impl == "plan":
        return []
    traffic, dims = cell.traffic, cell.config["model"]
    cfg = harness.model_config(cell)
    b, s, g = traffic["batch"], traffic["prompt_len"], traffic["gen_len"]
    on_device = SingleDeviceSharding(jax.devices()[0])

    def committed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=on_device), tree)

    prefill = serve.make_prefill_step(cfg, None, cell.a2a_impl,
                                      cache_len=s + g)
    params = committed(weights.param_shapes(dims))
    batch = committed({"tokens": jax.ShapeDtypeStruct((b, s), np.int32)})
    out = [prefill.lower(params, batch)]
    if g > 1:
        step = serve.make_serve_step(cfg, None, cell.a2a_impl)
        cache = committed(jax.eval_shape(prefill, params, batch)[1])
        toks = committed(jax.ShapeDtypeStruct((b,), np.int32))
        pos = jax.ShapeDtypeStruct((), np.int32)
        out.append(step.lower(params, cache, toks, pos))
    return out


def cell_programs(cell) -> List[str]:
    """Compiled HLO text of ``cell_lowered``'s programs."""
    return [lowered.compile().as_text() for lowered in cell_lowered(cell)]


# by cell name: a reader gets only the run, and the readers of one run
# share one table, so its programs are compiled once
_TABLES: Dict[str, Dict[str, Optional[str]]] = {}


def cell_scope_table(cell) -> Dict[str, Optional[str]]:
    """``hlo_scopes`` of the cell's programs, made once per process; empty
    where the program names no scopes.

    A persistent compile cache keyed without metadata may hold the same
    programs compiled from a tree that names no scopes: set-up then loaded
    and ran those, and the compile here loads them again.  The programs
    are then compiled anew under a key that holds the metadata, and each
    instruction of the programs that ran takes the scope of its line
    there (``aligned_scopes``)."""
    import jax

    names = program_scopes()
    if names is None:
        return {}
    if cell.name not in _TABLES:
        ran = cell_programs(cell)
        table = hlo_scopes(ran, names.DEVICE_SCOPES)
        flag = "jax_compilation_cache_include_metadata_in_key"
        if table and not any(table.values()) and not getattr(jax.config,
                                                             flag):
            jax.config.update(flag, True)
            try:
                scoped = cell_programs(cell)
            finally:
                jax.config.update(flag, False)
            table = aligned_scopes(ran, scoped, names.DEVICE_SCOPES) \
                or hlo_scopes(scoped, names.DEVICE_SCOPES)
        _TABLES[cell.name] = table if any(table.values()) else {}
    return _TABLES[cell.name]


def scope_us_per_token(run, pick) -> Optional[float]:
    """Chip-microseconds of own device time under the program scopes that
    ``pick(scopes)`` names, per token of the traced window; None where
    the program names no scopes or the run has no trace."""
    names = program_scopes()
    if (names is None or run.trace is None or not run.trace_devices
            or not run.tokens):
        return None
    table = cell_scope_table(run.cell)
    if not table:
        return None
    own = by_scope(scope_seconds(run.trace, run.trace_devices, run.trace_lo,
                                 run.trace_hi, table))
    seconds = sum(own.get(s, 0.0) for s in pick(names))
    if seconds <= 0:
        return None
    return 1e6 * seconds * len(run.trace_devices) / run.tokens


# -- host spans by thread ---------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    dur_ns: float
    thread: str
    stats: dict = dataclasses.field(default_factory=dict, compare=False,
                                    hash=False)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_spans(path: str) -> List[Span]:
    """The benchmark's and the program's host spans of a profile, each
    with its thread (one line of a host plane) and its stats."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    prefixes = (trace_reduce.HOST_SPAN_PREFIX,) + PROGRAM_SPAN_PREFIXES
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        # Python threads all read "python": the line's index tells them apart
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}#{i}:{line.name}"
            out.extend(Span(ev.name, float(ev.start_ns),
                            float(ev.duration_ns), thread, dict(ev.stats))
                       for ev in line.events
                       if ev.name.startswith(prefixes))
    return sorted(out, key=lambda s: s.start_ns)


def innermost(spans: Iterable[Span]) -> List[Tuple[float, float, str]]:
    """Disjoint (start, end, name) pieces, each named by the innermost of
    ``spans`` open over it.  Spans of one thread nest."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []        # (end, name), innermost last
    cursor = 0.0

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for sp in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
        close_until(sp.start_ns)
        if stack and sp.start_ns > cursor:
            out.append((cursor, sp.start_ns, stack[-1][1]))
        cursor = sp.start_ns
        stack.append((min(sp.end_ns, stack[-1][0]) if stack else sp.end_ns,
                      sp.name))
    close_until(float("inf"))
    return out


def _attribute(gaps: Sequence[Tuple[float, float]],
               pieces: Sequence[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Seconds of ``gaps`` under each piece's name, the rest ``none``."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        covered, k = 0.0, j
        while k < len(pieces) and pieces[k][0] < ge:
            ov = min(pieces[k][1], ge) - max(pieces[k][0], gs)
            if ov > 0:
                out[pieces[k][2]] += ov / 1e9
                covered += ov
            k += 1
        if ge - gs > covered:
            out["none"] += (ge - gs - covered) / 1e9
    return dict(out)


def window_thread(spans: Sequence[Span]) -> Optional[str]:
    """The host thread that runs the window (holds ``bench.window``)."""
    for sp in spans:
        if sp.name == trace_reduce.WINDOW_SPAN:
            return sp.thread
    return None


def thread_idle(trace: trace_reduce.Trace, spans: Sequence[Span],
                device: int, lo: float, hi: float) -> Dict[str, float]:
    """Idle seconds of one device by what the thread that runs the window
    was doing: each stretch of a gap goes to the innermost span open on
    that thread then, else ``none``."""
    thread = window_thread(spans)
    mine = [s for s in spans
            if s.thread == thread and s.name != trace_reduce.WINDOW_SPAN]
    gaps = trace_reduce.idle_gaps(trace.device_ops.get(device, ()), lo, hi)
    return _attribute(gaps, innermost(mine))


def _overlap_ns(a: Sequence[Tuple[float, float]],
                b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_within(trace: trace_reduce.Trace, spans: Sequence[Span],
                devices: Sequence[int], lo: float, hi: float,
                names: Iterable[str]) -> Dict[str, float]:
    """Device idle seconds inside and outside the union of the spans named
    ``names`` (on any thread) in [lo, hi), averaged over ``devices``,
    with the window's and the union's seconds."""
    names = frozenset(names)
    union = trace_reduce.merged(trace_reduce.clip(
        [s for s in spans if s.name in names], lo, hi))
    idle_in = idle_all = 0.0
    for d in devices:
        gaps = trace_reduce.idle_gaps(trace.device_ops.get(d, ()), lo, hi)
        idle_all += sum(e - s for s, e in gaps)
        idle_in += _overlap_ns(gaps, union)
    n = max(len(devices), 1)
    return {"window_s": (hi - lo) / 1e9,
            "spans_s": sum(e - s for s, e in union) / 1e9,
            "idle_in_s": idle_in / n / 1e9,
            "idle_out_s": (idle_all - idle_in) / n / 1e9}


def dispatch_ms_mean(spans: Sequence[Span], lo: float, hi: float
                     ) -> Optional[float]:
    """Mean milliseconds of the program's step dispatch spans that start
    in [lo, hi)."""
    names = program_scopes()
    if names is None:
        return None
    steps = (names.SERVE_PREFILL, names.SERVE_DECODE_STEP)
    durs = [s.dur_ns for s in spans
            if s.name in steps and lo <= s.start_ns < hi]
    return sum(durs) / len(durs) / 1e6 if durs else None


# -- the script -------------------------------------------------------------------

def report(trace: trace_reduce.Trace, spans: Sequence[Span],
           devices: Sequence[int], cell=None) -> dict:
    """What ``main`` prints for one kept trace."""
    lo, hi = trace_reduce.window_bounds(trace)
    busy = trace_reduce.device_busy_s(trace, devices, lo, hi)
    out = {"window_s": (hi - lo) / 1e9, "busy_s": busy,
           "idle_by_window_thread_s": trace_reduce.top(
               thread_idle(trace, spans, devices[0], lo, hi), 20),
           "dispatch_ms_mean": dispatch_ms_mean(spans, lo, hi)}
    names = program_scopes()
    if names is not None:
        d = idle_within(trace, spans, devices, lo, hi,
                        (names.PLAN_SUBMIT, names.PLAN_SYNTHESIZE))
        outside = d["window_s"] - d["spans_s"]
        out["daemon"] = dict(
            d, idle_share_in=d["idle_in_s"] / d["spans_s"]
            if d["spans_s"] > 0 else None,
            idle_share_out=d["idle_out_s"] / outside if outside > 0
            else None)
    if cell is not None:
        table = cell_scope_table(cell)
        own = scope_seconds(trace, devices, lo, hi, table)
        scoped = by_scope(own)
        total = sum(scoped.values())
        out["device_s_by_scope"] = {str(k): v for k, v in scoped.items()}
        out["scoped_share_of_busy"] = \
            (total - scoped.get(None, 0.0)) / total if total else None
        out["unscoped_ops_s"] = trace_reduce.top(
            {op: s for (scope, op), s in own.items() if scope is None})
    return out


def main(argv=None) -> int:
    from bench import cells

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or .xplane.pb file")
    ap.add_argument("--workload", default=None,
                    help="the cell whose programs name the device's ops")
    args = ap.parse_args(argv)
    trace = trace_reduce.load(args.trace)
    cell = cells.load_cell(args.workload) if args.workload else None
    devices = sorted(trace.device_ops)[:cell.chips if cell else None]
    if not devices:
        print("no device ops in the trace", file=sys.stderr)
        return 1
    print(json.dumps(report(trace, load_spans(args.trace), devices, cell)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
