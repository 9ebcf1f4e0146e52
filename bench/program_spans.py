"""The program's own host spans in a traced run's profile.

The scheduler writes ``serve.*`` spans through ``Tracer.phase`` (names
in ``runtime/server.py``): ``serve.step`` per loop iteration, and per
dispatch ``serve.<kind>.prepare`` / ``.put`` / ``.enqueue`` / ``.wait``
/ ``.commit``.  They are ``jax.profiler`` annotations, so they share
the device planes' clock.  ``load`` reads them, with the device planes,
once per run and keeps them on the reader's context; a program that
writes no such span gives a trace without host events, and the readers
that need them stay silent.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Optional

from bench import trace_reduce

PREFIX = "serve."
_CACHE = "program_trace"


def load(ctx) -> Optional[trace_reduce.Trace]:
    """The traced run's profile with the program's ``serve.*`` spans as
    its host events; None where the run wrote no profile."""
    if not hasattr(ctx, _CACHE):
        root = getattr(ctx.drv, "trace_dir", None)
        path = trace_reduce.find_trace(root) if root else None
        setattr(ctx, _CACHE, trace_reduce.load(path, span_prefix=PREFIX)
                if path else None)
    return getattr(ctx, _CACHE)


def named(trace: trace_reduce.Trace, name: str) -> List[trace_reduce.Event]:
    return sorted((e for e in trace.host if e.name == name),
                  key=lambda e: e.start)


def dispatch_gaps(trace: trace_reduce.Trace) -> List[float]:
    """Nanoseconds from each dispatch's ``serve.*.wait`` end to the next
    dispatch's ``serve.*.enqueue`` start, over consecutive pairs."""
    marks = sorted((e.start, e.end, e.name.rsplit(".", 1)[1])
                   for e in trace.host
                   if e.name.endswith((".enqueue", ".wait")))
    gaps, ready = [], None
    for start, end, what in marks:
        if what == "wait":
            ready = end
        elif ready is not None:
            gaps.append(start - ready)
            ready = None
    return gaps


def self_ns(trace: trace_reduce.Trace) -> Dict[str, float]:
    """Each span name's total self time: its spans' durations less the
    part the spans nested inside them cover."""
    spans = sorted(trace.host, key=lambda e: (e.start, -e.end))
    out: Dict[str, float] = collections.defaultdict(float)
    for i, e in enumerate(spans):
        inner = [(c.start, c.end) for c in spans[i + 1:]
                 if c.start < e.end and e.start <= c.start and c.end <= e.end]
        covered = sum(b - a for a, b in
                      trace_reduce.merge(trace_reduce.clip(inner, e.start,
                                                           e.end)))
        out[e.name] += (e.end - e.start) - covered
    return dict(out)
