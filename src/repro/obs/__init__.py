"""Low-overhead serving observability: per-request lifecycle tracing,
scheduler spans in the profiler's trace, a counters/gauges/histograms
registry with a no-op fast path, derived latency/phase/occupancy
views, time-windowed series, SLO/goodput accounting, and JSONL +
Chrome-trace exports (with windowed counter tracks).

Host-side only by construction — timestamps wrap jitted dispatches
(after ``block_until_ready()``), never enter them; the analyzer's
JX001/AST001 rules plus tests/test_obs.py's transfer-guard test keep
it that way.
"""

from repro.obs.metrics import (Counter, Gauge, Histogram,
                               MetricsRegistry, NullMetricsRegistry,
                               NULL_METRICS)
from repro.obs.tracer import (RequestRecord, Tracer, NullTracer,
                              NULL_TRACER)
from repro.obs.views import (gap_summary, occupancy_summary,
                             percentiles, phase_summary,
                             request_latency_summary, summary_table)
from repro.obs.windows import window_series, window_summary
from repro.obs.slo import (SLOSpec, attainment, goodput,
                           max_sustainable_rate, request_met,
                           slo_report)
from repro.obs.export import write_chrome_trace, write_jsonl

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "NullMetricsRegistry", "NULL_METRICS",
    "RequestRecord", "Tracer", "NullTracer", "NULL_TRACER",
    "percentiles", "request_latency_summary", "phase_summary",
    "gap_summary",
    "occupancy_summary", "summary_table",
    "window_series", "window_summary",
    "SLOSpec", "request_met", "attainment", "goodput", "slo_report",
    "max_sustainable_rate",
    "write_jsonl", "write_chrome_trace",
]
