"""Derived views over a serving trace: latency percentiles, phase
breakdowns (host, device-wait and gap time beside each phase's wall
time) and occupancy/utilization rollups.

These are pure post-hoc reductions over the tracer's host-side event
log and metrics registry — nothing here runs during serving, so the
views can be as expensive as they like without touching the serving
hot path.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

from repro.obs.metrics import MetricsRegistry
from repro.obs.tracer import Tracer

__all__ = ["percentiles", "request_latency_summary", "phase_summary",
           "gap_summary", "occupancy_summary", "summary_table"]

_QS = (50, 95, 99)


def percentiles(xs: Sequence[float], qs: Sequence[int] = _QS
                ) -> Dict[str, float]:
    """Nearest-rank percentiles + mean.

    An empty input yields ``count=0`` with every statistic NaN-marked:
    a window (or run) with zero finished requests has *undefined*
    latency, and a silent 0.0 would read as an impossibly fast p99
    downstream (dashboards, the regression gate).  Consumers branch on
    ``count`` before comparing.
    """
    if not xs:
        nan = float("nan")
        return {**{f"p{q}": nan for q in qs}, "mean": nan, "count": 0}
    s = sorted(xs)
    out = {}
    for q in qs:
        rank = max(1, math.ceil(q / 100.0 * len(s)))
        out[f"p{q}"] = s[min(rank, len(s)) - 1]
    out["mean"] = sum(s) / len(s)
    out["count"] = len(s)
    return out


def request_latency_summary(tracer: Tracer) -> Dict[str, Dict[str, float]]:
    """TTFT / TPOT / queue-delay / end-to-end percentiles over every
    finished request in the trace (paper-style latency reporting:
    TTFT = first token - enqueue, TPOT = inter-token mean after the
    first)."""
    recs = tracer.request_records()
    cols = {"ttft_s": [], "tpot_s": [], "queue_delay_s": [], "e2e_s": []}
    for r in recs:
        for k in cols:
            v = getattr(r, k)
            if v is not None:
                cols[k].append(v)
    return {k: percentiles(v) for k, v in cols.items()}


def phase_summary(metrics: MetricsRegistry) -> Dict[str, Dict[str, float]]:
    """Per-phase dispatch counts + wall-time totals (prefill chunk
    steps vs decode spans vs spec verify steps), straight from the
    registry the scheduler feeds.  Each phase's wall time splits into
    ``host_s`` (method entry to the jitted call's return) and
    ``wait_s`` (that return to ``block_until_ready``); the time between
    dispatches is ``gap_summary``'s."""
    out: Dict[str, Dict[str, float]] = {}
    for phase in ("prefill", "span", "verify"):
        h = metrics.hist(f"serving.wall_s.{phase}")
        n = metrics.counter_value(f"serving.dispatches.{phase}")
        out[phase] = {
            "dispatches": n,
            "wall_s": h.total if h is not None else 0.0,
            "host_s": metrics.hist_total(f"serving.host_s.{phase}"),
            "wait_s": metrics.hist_total(f"serving.wait_s.{phase}"),
            "mean_dispatch_s": (h.total / h.count
                                if h is not None and h.count else 0.0),
        }
    total = sum(p["wall_s"] for p in out.values())
    for p in out.values():
        p["wall_frac"] = p["wall_s"] / total if total > 0 else 0.0
    return out


def gap_summary(metrics: MetricsRegistry) -> Dict[str, float]:
    """Host seconds from one dispatch's ready stamp to the next
    dispatch's call (``serving.gap_s``): the device's idle time between
    steps while no dispatch overlaps another."""
    g = metrics.hist("serving.gap_s")
    n = g.count if g is not None else 0
    return {"count": n, "gap_s": g.total if n else 0.0,
            "mean_gap_s": g.mean if n else 0.0}


def occupancy_summary(metrics: MetricsRegistry) -> Dict[str, float]:
    """Chunk-occupancy (packed tokens per chunk_step over B*chunk
    capacity) and span-utilization (productive slot-steps over B*span)
    rollups."""
    out = {}
    occ = metrics.hist("serving.chunk.occupancy")
    util = metrics.hist("serving.span.utilization")
    out["chunk_occupancy_mean"] = occ.mean if occ is not None else 0.0
    out["span_utilization_mean"] = util.mean if util is not None else 0.0
    pool = metrics.gauge("serving.pool.blocks_in_use")
    out["peak_blocks_in_use"] = (pool.peak if pool.samples else 0.0)
    return out


def summary_table(tracer: Tracer) -> str:
    """Human-readable trace summary for launch/serve.py --trace."""
    lines: List[str] = []
    lat = request_latency_summary(tracer)
    phases = phase_summary(tracer.metrics)
    occ = occupancy_summary(tracer.metrics)
    gap = gap_summary(tracer.metrics)

    lines.append("  trace: %d events, %d requests"
                 % (len(tracer.events), len(tracer.requests)))
    hdr = f"  {'latency':<14}{'p50':>10}{'p95':>10}{'p99':>10}{'mean':>10}"
    lines.append(hdr)
    for key, label in (("queue_delay_s", "queue-delay"),
                       ("ttft_s", "ttft"), ("tpot_s", "tpot"),
                       ("e2e_s", "e2e")):
        d = lat[key]
        lines.append("  %-14s%10.2f%10.2f%10.2f%10.2f ms"
                     % (label, d["p50"] * 1e3, d["p95"] * 1e3,
                        d["p99"] * 1e3, d["mean"] * 1e3))
    lines.append(f"  {'phase':<14}{'dispatches':>10}{'wall_s':>10}"
                 f"{'host_s':>10}{'wait_s':>10}{'frac':>10}")
    for phase, d in phases.items():
        lines.append("  %-14s%10d%10.3f%10.3f%10.3f%10.2f"
                     % (phase, d["dispatches"], d["wall_s"], d["host_s"],
                        d["wait_s"], d["wall_frac"]))
    lines.append("  %-14s%10d%10.3f  (host time between dispatches)"
                 % ("gap", gap["count"], gap["gap_s"]))
    lines.append("  chunk-occupancy=%.2f span-utilization=%.2f "
                 "peak-blocks=%d"
                 % (occ["chunk_occupancy_mean"],
                    occ["span_utilization_mean"],
                    occ["peak_blocks_in_use"]))
    return "\n".join(lines)
