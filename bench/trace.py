"""Reduction of a profiler trace (``*.xplane.pb``) to device busy time,
per-operation device time and idle gaps attributed to host spans.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, named by their HLO instruction (an event's
name is the instruction's text, ``%name = ...``).  Host spans are the
benchmark's own
``jax.profiler.TraceAnnotation`` events, whose names start with
``bench.``; they share the trace's clock with the device events.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH = "DoEnqueueProgram"     # the host's launch of one program
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Trace:
    ops: dict              # plane name -> [(name, start_ns, end_ns)]
    spans: list            # [(name, start_ns, end_ns)] host spans
    shift_ns: float = 0.0  # added to device times (see ``load``)


def find(trace_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler.trace`` run wrote."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, f"expected one xplane file, found {paths}"
    return paths[0]


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def load(path: str) -> Trace:
    """Device operations and host spans on one clock.

    The device's times reach the trace converted to the host's clock
    with an offset: on a v5e, programs appeared to start about 1.45 ms
    before the host had launched them (the recorded test trace).  With
    one device, the k-th program execution (``XLA Modules``) belongs to
    the k-th host launch (``DoEnqueueProgram``), and none can start
    before its launch ended: device times are moved later by the
    largest such lead.  Otherwise they are left as they are."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans, launches = {}, {}, [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops[plane.name] = [
                (op_name(e.name), float(e.start_ns), float(e.end_ns))
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
            modules[plane.name] = sorted(
                float(e.start_ns) for line in plane.lines
                if line.name == MODULES_LINE for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      float(e.start_ns), float(e.end_ns)))
                    elif e.name == LAUNCH:
                        launches.append(float(e.end_ns))
    shift = 0.0
    if len(modules) == 1:
        (starts,) = modules.values()
        launches.sort()
        if starts and len(starts) == len(launches):
            shift = max(0.0, max(e - d for d, e in zip(starts, launches)))
            ops = {k: [(n, s + shift, e + shift) for n, s, e in v]
                   for k, v in ops.items()}
    return Trace(ops, spans, shift)


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window(tr: Trace, name: str = "window") -> tuple[float, float]:
    """The traced window: the benchmark's span of that name around the
    measured loop."""
    found = [(s, e) for n, s, e in tr.spans if n == name]
    assert len(found) == 1, f"{len(found)} '{name}' spans in the trace"
    return found[0]


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Device busy time in [lo, hi], averaged over the device planes."""
    per = [sum(e - s for s, e in union([(s, e) for _, s, e in ev], lo, hi))
           for ev in tr.ops.values()]
    return float(np.mean(per)) if per else 0.0


def op_time_ns(tr: Trace, prefix: str, lo: float, hi: float) -> float:
    """Summed device time of operations named ``prefix`` or
    ``prefix.<n>`` (a compiled kernel's instructions), in [lo, hi],
    averaged over the device planes."""
    pat = re.compile(rf"^{re.escape(prefix)}(\.\d+)?$")
    per = [sum(min(e, hi) - max(s, lo) for n, s, e in ev
               if pat.match(n) and e > lo and s < hi)
           for ev in tr.ops.values()]
    return float(np.mean(per)) if per else 0.0


def op_count(tr: Trace, prefix: str, lo: float, hi: float) -> int:
    pat = re.compile(rf"^{re.escape(prefix)}(\.\d+)?$")
    return max((sum(1 for n, s, e in ev if pat.match(n) and lo <= s < hi)
                for ev in tr.ops.values()), default=0)


def leaves(events):
    """The events that contain no other event: a loop's or a call's
    event spans the operations of its body, which are listed too."""
    ev = sorted(events, key=lambda x: (x[1], -x[2]))
    return [x for i, x in enumerate(ev)
            if not (i + 1 < len(ev) and ev[i + 1][1] < x[2]
                    and ev[i + 1][2] <= x[2])]


def top_ops(tr: Trace, lo: float, hi: float, n: int = 10):
    """[(op name, seconds)] of the operations with the most device time
    (first device plane), loops and calls counted through their
    bodies."""
    acc: dict[str, float] = {}
    for ev in list(tr.ops.values())[:1]:
        for name, s, e in leaves(ev):
            if e > lo and s < hi:
                acc[name] = acc.get(name, 0.0) + min(e, hi) - max(s, lo)
    return [[k, v * 1e-9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def _segments(spans, lo: float, hi: float):
    """[lo, hi] cut at every span boundary, each piece labelled with the
    innermost span covering it ("none" where none does).  Spans come
    from one host thread's nested calls, so a stack finds the innermost
    one."""
    marks = sorted([(s, 1, -(e - s), n) for n, s, e in spans]
                   + [(e, 0, 0.0, n) for n, s, e in spans])
    segs, stack, t = [], [], lo
    for x, is_start, _, name in marks:
        x = min(max(x, lo), hi)
        if x > t:
            segs.append((t, x, stack[-1] if stack else "none"))
            t = x
        if is_start:
            stack.append(name)
        elif name in stack:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
    if t < hi:
        segs.append((t, hi, stack[-1] if stack else "none"))
    return segs


def idle_gaps(tr: Trace, lo: float, hi: float, n: int = 10):
    """[(host span, seconds)]: the device's idle time in [lo, hi] (first
    device plane), split over the innermost host span at each instant
    ("none" where no span is open), summed by span name, longest
    first."""
    ev = list(tr.ops.values())[:1]
    busy = union([(s, e) for _, s, e in ev[0]], lo, hi) if ev else []
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if t < hi:
        gaps.append((t, hi))
    acc: dict[str, float] = {}
    segs = _segments(tr.spans, lo, hi)
    i = 0
    for g0, g1 in gaps:
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < g1:
            a, b, name = segs[j]
            ov = min(b, g1) - max(a, g0)
            if ov > 0:
                acc[name] = acc.get(name, 0.0) + ov
            j += 1
    return [[k, v * 1e-9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
