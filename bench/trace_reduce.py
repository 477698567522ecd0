"""Reduce a JAX profiler trace to the benchmark's device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO instruction, named by the instruction's HLO text
(``%fusion.3 = bf16[...] fusion(...)``).  A ``while`` or other control op
encloses the ops of its body on the same line, so busy time is the union
of intervals and an op's own time is its duration less that of the ops
nested in it.  Host planes (``/host:CPU``) carry the benchmark's own
``TraceAnnotation`` spans, named ``bench.*``, on the same clock.

Everything below works on plain lists, so tests can check it on small
hand-made traces as well as on a recorded one.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HOST_SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """Device ops per device id, and the benchmark's host spans."""

    device_ops: Dict[int, List[Event]]
    host_spans: List[Event]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or a directory holding one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    device_ops: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                ops.extend(Event(ev.name, float(ev.start_ns),
                                 float(ev.duration_ns))
                           for ev in line.events)
            device_ops[int(m.group(1))] = sorted(ops,
                                                 key=lambda e: e.start_ns)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(Event(ev.name, float(ev.start_ns),
                                  float(ev.duration_ns))
                            for ev in line.events
                            if ev.name.startswith(HOST_SPAN_PREFIX))
    return Trace(device_ops=device_ops,
                 host_spans=sorted(host, key=lambda e: e.start_ns))


# -- intervals ----------------------------------------------------------------

def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to the window [lo, hi) (ns); those outside dropped."""
    out = []
    for ev in events:
        s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
        if e > s:
            out.append(Event(ev.name, s, e - s))
    return out


def merged(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Union of the events' intervals, as sorted disjoint (start, end)."""
    spans: List[Tuple[float, float]] = []
    for s, e in sorted((ev.start_ns, ev.end_ns) for ev in events):
        if spans and s <= spans[-1][1]:
            if e > spans[-1][1]:
                spans[-1] = (spans[-1][0], e)
        else:
            spans.append((s, e))
    return spans


def busy_ns(events: Iterable[Event]) -> float:
    return sum(e - s for s, e in merged(events))


def idle_gaps(events: Iterable[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """Stretches of [lo, hi) in which no event runs."""
    gaps, cursor = [], lo
    for s, e in merged(events):
        if s > cursor:
            gaps.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    return [(s, e) for s, e in gaps if e > s]


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event with its own time: its duration less the union of the
    events nested inside it (control ops enclose their body's ops)."""
    order = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    children: Dict[int, List[Event]] = defaultdict(list)
    stack: List[int] = []
    for i, ev in enumerate(order):
        while stack and order[stack[-1]].end_ns <= ev.start_ns:
            stack.pop()
        if stack and ev.end_ns <= order[stack[-1]].end_ns:
            children[stack[-1]].append(ev)
        stack.append(i)
    return [(ev, ev.dur_ns - busy_ns(children[i]))
            for i, ev in enumerate(order)]


# -- HLO text of an op event ----------------------------------------------------

_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def instruction(name: str) -> str:
    """``fusion.3`` from ``%fusion.3 = bf16[...] fusion(...)``."""
    head = name.split(" = ", 1)[0]
    return head.lstrip("%").strip()


def _split_result(name: str) -> Tuple[str, str]:
    """(result shape text, rest starting at the opcode)."""
    if " = " not in name:
        return "", name
    rhs = name.split(" = ", 1)[1]
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rhs[:i + 1], rhs[i + 1:].lstrip()
        return rhs, ""
    parts = rhs.split(" ", 1)
    return parts[0], parts[1] if len(parts) > 1 else ""


def opcode(name: str) -> str:
    """HLO opcode of an op event (``fusion``, ``custom-call``, ...)."""
    rest = _split_result(name)[1]
    return rest.split("(", 1)[0].strip()


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every ``dtype[d0,d1,...]`` in ``text``, in order."""
    out = []
    for dtype, dims in _SHAPE.findall(text):
        out.append((dtype, tuple(int(x) for x in dims.split(",") if x)))
    return out


def result_and_operands(name: str):
    """(result shapes, operand shapes) of an op event's HLO text."""
    result, rest = _split_result(name)
    args = rest.split("(", 1)[1] if "(" in rest else ""
    depth, end = 1, len(args)
    for i, ch in enumerate(args):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            end = i
            break
    return shapes(result), shapes(args[:end])


def short_name(name: str) -> str:
    """Instruction name, opcode and result shape: stable enough to rank."""
    res = shapes(_split_result(name)[0])
    shape = f" {res[0][0]}[{','.join(map(str, res[0][1]))}]" if res else ""
    return f"{instruction(name)} {opcode(name)}{shape}"[:120]


# -- reductions -----------------------------------------------------------------

def device_busy_s(trace: Trace, devices: Sequence[int], lo: float,
                  hi: float) -> float:
    """Busy seconds in [lo, hi), averaged over ``devices``."""
    vals = [busy_ns(clip(trace.device_ops.get(d, ()), lo, hi)) / 1e9
            for d in devices]
    return sum(vals) / len(vals) if vals else 0.0


def op_self_seconds(trace: Trace, devices: Sequence[int], lo: float,
                    hi: float, select=None) -> Dict[str, float]:
    """Own seconds per op (``short_name``), averaged over ``devices``;
    ``select(event_name)`` keeps a subset."""
    total: Dict[str, float] = defaultdict(float)
    for d in devices:
        for ev, own in self_times(clip(trace.device_ops.get(d, ()), lo,
                                       hi)):
            if select is None or select(ev.name):
                total[short_name(ev.name)] += own / 1e9 / len(devices)
    return dict(total)


def labelled_idle(trace: Trace, device: int, lo: float, hi: float
                  ) -> Dict[str, float]:
    """Idle seconds of one device by what the host was doing: each gap
    goes to the ``bench.*`` span that overlaps it most, else ``none``."""
    gaps = idle_gaps(trace.device_ops.get(device, ()), lo, hi)
    spans = [s for s in trace.host_spans if s.name != WINDOW_SPAN]
    starts = [s.start_ns for s in spans]
    out: Dict[str, float] = defaultdict(float)
    for gs, ge in gaps:
        best, best_ov = "none", 0.0
        # spans starting before the gap's end; long spans may start early
        for sp in spans[:bisect.bisect_left(starts, ge)]:
            ov = min(sp.end_ns, ge) - max(sp.start_ns, gs)
            if ov > best_ov:
                best, best_ov = sp.name, ov
        out[best] += (ge - gs) / 1e9
    return dict(out)


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace: Trace, devices: Sequence[int], lo: float, hi: float,
              n: int = 10) -> dict:
    if not devices:
        return {"device_ops": [], "idle_gaps": []}
    return {"device_ops": top(op_self_seconds(trace, devices, lo, hi), n),
            "idle_gaps": top(labelled_idle(trace, devices[0], lo, hi), n)}


def is_collective(name: str) -> bool:
    op, ins = opcode(name), instruction(name)
    return any(c in op or c in ins
               for c in ("collective-permute", "all-to-all"))


def is_tpu_kernel(name: str) -> bool:
    return ('custom_call_target="tpu_custom_call"' in name
            and opcode(name) == "custom-call")


WINDOW_SPAN = "bench.window"


def window_bounds(trace: Trace) -> Tuple[float, float]:
    """The traced window on the trace's clock: the host span that the
    benchmark opens around its measured loop, else first to last op."""
    for sp in trace.host_spans:
        if sp.name == WINDOW_SPAN:
            return sp.start_ns, sp.end_ns
    evs = [ev for ops in trace.device_ops.values() for ev in ops]
    if not evs:
        return 0.0, 0.0
    return (min(e.start_ns for e in evs), max(e.end_ns for e in evs))
