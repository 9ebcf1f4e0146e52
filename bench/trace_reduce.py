"""From a profiler trace to device busy time, per-program device time,
the costliest device ops and the longest idle gaps.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are named ``/device:TPU:<n>``; on each, the line ``XLA Ops``
holds one event per device operation and ``XLA Modules`` one per
program execution.  Host planes hold the benchmark's own
``TraceAnnotation`` spans (names starting ``bench.``).  Times are in
nanoseconds; the device planes' clock agrees with the host's to about a
millisecond.  An op is named by its HLO instruction (the text before
`` = ``).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclasses.dataclass
class Event:
    name: str
    start: float                        # ns
    end: float                          # ns


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]         # device plane -> op events
    modules: Dict[str, List[Event]]     # device plane -> program events
    host: List[Event]                   # benchmark spans on the host


def find_trace(root: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str, span_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            ops[plane.name] = _events(lines["XLA Ops"])
            modules[plane.name] = (_events(lines["XLA Modules"])
                                   if "XLA Modules" in lines else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e.name.startswith(span_prefix))
    return Trace(ops, modules, host)


def _events(line) -> List[Event]:
    return [Event(e.name.split(" = ", 1)[0], float(e.start_ns),
                  float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Time in [lo, hi] in which at least one event runs."""
    return sum(b - a for a, b in
               merge(clip([(e.start, e.end) for e in events], lo, hi)))


def window(trace: Trace) -> Optional[Interval]:
    """The traced window: from the first benchmark span's start to the
    last one's end (the profiler starts just before the first and stops
    just after the last)."""
    if not trace.host:
        return None
    return (min(e.start for e in trace.host),
            max(e.end for e in trace.host))


def busy_and_window(trace: Trace) -> Optional[Tuple[float, float]]:
    """(busy seconds averaged over the device planes, window seconds)."""
    w = window(trace)
    if w is None or not trace.ops:
        return None
    busy = [busy_ns(evs, *w) for evs in trace.ops.values()]
    return sum(busy) / len(busy) * 1e-9, (w[1] - w[0]) * 1e-9


def program_time(trace: Trace, spans: Sequence[Event], program: str
                 ) -> Tuple[Optional[List[float]], List[str]]:
    """Device seconds of each execution of the programs whose name
    contains ``program``, paired in order with ``spans``: the host spans
    around the dispatches of that program, each of which runs one
    execution and waits for it, so the i-th execution in the trace
    belongs to the i-th span.  (Host and device clocks in a trace agree
    only to about a millisecond, so time containment would misplace
    short executions.)  Averaged over device planes; None where a plane
    holds another number of executions than there are spans.  With the
    program names found."""
    names = set()
    per: Optional[List[float]] = None
    for evs in trace.modules.values():
        mods = sorted((e for e in evs if program in e.name),
                      key=lambda e: e.start)
        if len(mods) != len(spans):
            return None, sorted(names)
        names.update(e.name for e in mods)
        secs = [(e.end - e.start) * 1e-9 / len(trace.modules) for e in mods]
        per = secs if per is None else [a + b for a, b in zip(per, secs)]
    return per, sorted(names)


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` device ops (by name) that took most time in the traced
    window, summed over their executions, averaged over planes."""
    w = window(trace)
    acc: Dict[str, float] = collections.defaultdict(float)
    for evs in trace.ops.values():
        for e in evs:
            if w is None or (e.end > w[0] and e.start < w[1]):
                acc[e.name] += (e.end - e.start) * 1e-9 / len(trace.ops)
    return [[n, s] for n, s in sorted(acc.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` longest device-idle gaps in the traced window, each
    named by the innermost benchmark span on the host that covers its
    middle (``host: outside any span`` where none does)."""
    w = window(trace)
    if w is None or not trace.ops:
        return []
    evs = next(iter(trace.ops.values()))
    busy = merge(clip([(e.start, e.end) for e in evs], *w))
    edges = [w[0]] + [x for iv in busy for x in iv] + [w[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        mid = (a + b) / 2
        cover = [e for e in trace.host if e.start <= mid <= e.end]
        name = (min(cover, key=lambda e: e.end - e.start).name if cover
                else "host: outside any span")
        out.append([name, (b - a) * 1e-9])
    return out
