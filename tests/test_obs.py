"""Serving telemetry (src/repro/obs): metrics/tracer unit tests with a
fake clock, export round-trips, derived-view math, and the end-to-end
contract the tentpole hangs on — tracing a full-featured ChunkedServer
(paged pool + prefix cache + spec decode) changes NOTHING about the
serving computation: greedy outputs stay bit-identical, compile counts
stay equal, and a traced steady-state wave still serves under
``jax.transfer_guard("disallow")`` (instrumentation is host-side only,
around dispatches — see ROADMAP "Serving telemetry")."""

import json

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import api
from repro.obs import (MetricsRegistry, NULL_METRICS, NULL_TRACER,
                       Tracer, occupancy_summary, percentiles,
                       phase_summary, request_latency_summary,
                       summary_table, write_jsonl, write_chrome_trace)
from repro.runtime.prefix_cache import BlockPool, RadixPrefixCache
from repro.runtime.server import (ChunkedServer, clone_requests,
                                  repetitive_requests,
                                  sharegpt_like_requests,
                                  sysprompt_sharegpt_requests)


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    m = MetricsRegistry()
    m.counter("c").inc()
    m.counter("c").inc(3)
    assert m.counter_value("c") == 4
    assert m.counter_value("missing", default=7) == 7

    g = m.gauge("g")
    g.set(2.0)
    g.set(5.0)
    g.set(1.0)
    assert g.value == 1.0 and g.peak == 5.0 and g.samples == 3

    h = m.histogram("h")
    for v in (3.0, 1.0, 2.0, 4.0):
        h.record(v)
    assert h.count == 4 and h.total == 10.0
    assert h.min == 1.0 and h.max == 4.0 and h.mean == 2.5
    assert m.hist_total("h") == 10.0
    assert m.hist("nope") is None


def test_histogram_nearest_rank_percentile():
    h = MetricsRegistry().histogram("h")
    for v in range(1, 101):        # 1..100
        h.record(float(v))
    assert h.percentile(50) == 50.0
    assert h.percentile(95) == 95.0
    assert h.percentile(99) == 99.0
    assert h.percentile(100) == 100.0
    empty = MetricsRegistry().histogram("e")
    assert empty.percentile(50) == 0.0


def test_registry_reset_and_snapshot():
    m = MetricsRegistry()
    m.counter("a").inc(2)
    m.gauge("b").set(1.5)
    m.histogram("c").record(0.25)
    snap = m.snapshot()
    assert snap["counters"]["a"] == 2
    assert snap["gauges"]["b"]["peak"] == 1.5
    assert snap["histograms"]["c"]["p50"] == 0.25
    m.reset()
    assert m.snapshot() == {"counters": {}, "gauges": {},
                            "histograms": {}}


def test_null_registry_is_inert():
    NULL_METRICS.counter("x").inc(100)
    NULL_METRICS.gauge("y").set(9.0)
    NULL_METRICS.histogram("z").record(1.0)
    assert NULL_METRICS.counter_value("x") == 0
    assert NULL_METRICS.hist("z") is None
    assert NULL_METRICS.snapshot() == {"counters": {}, "gauges": {},
                                       "histograms": {}}


def test_percentiles_view_nearest_rank():
    xs = [float(v) for v in range(1, 11)]       # 1..10
    p = percentiles(xs)
    assert p == {"p50": 5.0, "p95": 10.0, "p99": 10.0, "mean": 5.5,
                 "count": 10}
    assert p["p50"] <= p["p95"] <= p["p99"]
    assert percentiles([])["count"] == 0


# ----------------------------------------------------------------------
# tracer lifecycle with a deterministic clock
# ----------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _fake_traced_request():
    """One request through the lifecycle on integer timestamps:
    enqueue@1 admit@2 first_token@3 finish@4 with 5 output tokens."""
    tr = Tracer(clock=FakeClock())
    tr.enqueue(0, n_prompt=8, max_output=5)
    tr.admit(0, slot=2, cached_tokens=4, truncated=False)
    tr.first_token(0)
    tr.finish(0, n_out=5)
    return tr


def test_request_record_derived_latencies():
    tr = _fake_traced_request()
    (rec,) = tr.request_records()
    assert rec.queue_delay_s == 1.0     # admit@2 - enqueue@1
    assert rec.ttft_s == 2.0            # first@3 - enqueue@1
    assert rec.tpot_s == (4.0 - 3.0) / (5 - 1)
    assert rec.e2e_s == 3.0             # done@4 - enqueue@1
    assert rec.slot == 2 and rec.cached_tokens == 4
    kinds = [k for _, k, _ in tr.events]
    assert kinds == ["enqueue", "admit", "first_token", "finish"]


def test_first_token_and_finish_are_idempotent():
    tr = _fake_traced_request()
    (rec,) = tr.request_records()
    t_first, t_done = rec.t_first_token, rec.t_done
    tr.first_token(0)
    tr.finish(0, n_out=99)
    assert rec.t_first_token == t_first and rec.t_done == t_done
    assert rec.n_out == 5               # second finish ignored
    assert len(tr.events) == 4


def test_unfinished_request_yields_none_latencies():
    tr = Tracer(clock=FakeClock())
    tr.enqueue(1, n_prompt=4, max_output=8)
    (rec,) = tr.request_records()
    assert rec.ttft_s is None and rec.tpot_s is None
    assert rec.e2e_s is None and rec.queue_delay_s is None
    lat = request_latency_summary(tr)
    assert lat["ttft_s"]["count"] == 0


def test_clear_keeps_meta_resets_metrics():
    tr = _fake_traced_request()
    tr.meta["block_size"] = 16
    tr.metrics.counter("serving.dispatches.prefill").inc()
    tr.clear()
    assert tr.events == [] and tr.requests == {}
    assert tr.meta == {"block_size": 16}
    assert tr.metrics.counter_value("serving.dispatches.prefill") == 0


def test_null_tracer_is_inert():
    NULL_TRACER.enqueue(0, 1, 1)
    NULL_TRACER.event("x", foo=1)
    NULL_TRACER.span("y", 0.0, 1.0)
    assert not NULL_TRACER.enabled
    assert NULL_TRACER.events == [] and NULL_TRACER.request_records() == []


# ----------------------------------------------------------------------
# exports
# ----------------------------------------------------------------------

def test_jsonl_export_round_trips(tmp_path):
    tr = _fake_traced_request()
    tr.meta["block_size"] = 16
    # numpy scalars in args must serialize via the .item() hook
    tr.event("cow_resolve", slot=np.int64(3), src=np.int32(1), dst=2)
    path = tmp_path / "trace.jsonl"
    n = write_jsonl(tr, str(path))
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == n == 1 + 1 + len(tr.events)
    assert lines[0]["type"] == "meta" and lines[0]["block_size"] == 16
    assert lines[1]["type"] == "request" and lines[1]["ttft_s"] == 2.0
    events = [l for l in lines if l["type"] == "event"]
    ts = [l["t"] for l in events]
    assert ts == sorted(ts)
    (cow,) = [l for l in events if l["kind"] == "cow_resolve"]
    assert cow["slot"] == 3 and isinstance(cow["slot"], int)


def test_chrome_trace_export(tmp_path):
    tr = _fake_traced_request()
    tr.span("span_dispatch", 10.0, 10.5, steps=8, n_active=2,
            kv_lens=(32, 17))
    path = tmp_path / "trace.json"
    n = write_chrome_trace(tr, str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert len(evs) == n
    (x,) = [e for e in evs if e["ph"] == "X" and
            e["name"] == "span_dispatch"]
    assert x["dur"] == pytest.approx(0.5e6)
    assert x["args"]["kv_lens"] == [32, 17]     # tuple -> list
    # the finished request shows up as a slot-track window
    assert any(e["ph"] == "X" and e["name"] == "req 0" for e in evs)
    # empty tracer still writes a valid doc
    assert write_chrome_trace(Tracer(clock=FakeClock()),
                              str(tmp_path / "e.json")) == 0


# ----------------------------------------------------------------------
# prefix-cache instrumentation (unit level)
# ----------------------------------------------------------------------

def test_prefix_cache_records_lookups_and_evictions():
    tr = Tracer(clock=FakeClock())
    pool = BlockPool(8)
    tree = RadixPrefixCache(pool, 4, tracer=tr, metrics=tr.metrics)
    rng = np.random.default_rng(0)
    run = rng.integers(0, 100, 12).astype(np.int32)
    blocks = [pool.alloc() for _ in range(3)]
    tree.insert(run, blocks)
    for b in blocks:
        pool.decref(b)                  # cached-only -> evictable
    full, _, _ = tree.match(run)
    assert full == blocks
    m = tr.metrics
    assert m.counter_value("serving.prefix.lookups") == 1
    # hits are the server's admission's to count, once per request
    assert m.counter_value("serving.prefix.hits") == 0
    assert m.counter_value("serving.prefix.hit_tokens") == 0
    assert tree.evict(3) == 3
    assert m.counter_value("serving.prefix.evictions") == 3
    kinds = [k for _, k, _ in tr.events]
    assert "prefix_lookup" in kinds and "eviction" in kinds


# ----------------------------------------------------------------------
# end-to-end: tracing must not change the computation
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("yi-6b")
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


SRV_KW = dict(batch_slots=3, max_len=64, chunk=8, span=4,
              block_size=8, prefix_cache=True, spec_decode=3)


def test_traced_serving_identical_outputs_and_compiles(setup):
    cfg, params = setup
    reqs = sharegpt_like_requests(6, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=3)
    tracer = Tracer()
    traced = ChunkedServer(cfg, params, tracer=tracer, **SRV_KW)
    plain = ChunkedServer(cfg, params, **SRV_KW)
    a, b = clone_requests(reqs), clone_requests(reqs)
    traced.serve(a)
    plain.serve(b)
    for ra, rb in zip(a, b):
        assert ra.output == rb.output, (ra.rid, ra.output, rb.output)
    assert traced.compile_counts() == plain.compile_counts()

    # the trace actually observed the run
    assert len(tracer.requests) == len(reqs)
    recs = tracer.request_records()
    assert all(r.t_done is not None for r in recs)
    assert all(r.ttft_s is not None and r.ttft_s >= 0 for r in recs)
    lat = request_latency_summary(tracer)
    assert lat["ttft_s"]["count"] == len(reqs)
    assert lat["ttft_s"]["p50"] <= lat["ttft_s"]["p99"]

    m = tracer.metrics
    assert m.counter_value("serving.dispatches.prefill") > 0
    assert (m.counter_value("serving.dispatches.span")
            + m.counter_value("serving.dispatches.verify")) > 0
    assert m.counter_value("serving.requests.admitted") == len(reqs)
    assert m.counter_value("serving.requests.harvested") == len(reqs)
    assert m.counter_value("serving.prefix.lookups") == len(reqs)

    phases = phase_summary(m)
    assert phases["prefill"]["dispatches"] > 0
    assert sum(p["wall_frac"] for p in phases.values()) == \
        pytest.approx(1.0)
    occ = occupancy_summary(m)
    assert 0 < occ["chunk_occupancy_mean"] <= 1.0
    assert occ["peak_blocks_in_use"] > 0

    assert "ttft" in summary_table(tracer)

    # untraced server still derives its phase split from the registry
    assert plain.metrics.counter_value("serving.dispatches.prefill") > 0


@pytest.fixture(scope="module")
def traced_server(setup):
    cfg, params = setup
    tracer = Tracer()
    return ChunkedServer(cfg, params, tracer=tracer, **SRV_KW), tracer


@pytest.mark.parametrize("mix", ["sharegpt", "sysprompt", "repetitive"])
def test_latency_percentiles_ordered_on_every_mix(setup, traced_server,
                                                  mix):
    """Each mix served through one traced server (one wave after
    another, as the tracer is cleared between them) yields a record per
    request and, for TTFT, TPOT, queue delay and end-to-end latency,
    nearest-rank percentiles with p50 <= p95 <= p99 over every
    request."""
    cfg, _ = setup
    srv, tracer = traced_server
    V = cfg.vocab_size
    reqs = {"sharegpt": lambda: sharegpt_like_requests(
                6, V, max_input=16, max_output=8, seed=3),
            "sysprompt": lambda: sysprompt_sharegpt_requests(
                6, V, num_templates=2, template_len=12, max_input=20,
                max_output=6, seed=4),
            "repetitive": lambda: repetitive_requests(
                4, V, motif_len=4, reps=3, max_output=10, seed=5)}[mix]()
    tracer.clear()
    srv.serve(reqs)
    lat = request_latency_summary(tracer)
    for k in ("ttft_s", "tpot_s", "queue_delay_s", "e2e_s"):
        p = lat[k]
        assert p["count"] == len(reqs), (k, p)
        assert 0.0 <= p["p50"] <= p["p95"] <= p["p99"], (k, p)


def test_traced_steady_state_wave_is_transfer_free(setup):
    """A traced warm wave must stay inside the transfer-free serving
    contract: instrumentation reads only host mirrors, so
    transfer_guard('disallow') cannot fire."""
    cfg, params = setup
    reqs = sharegpt_like_requests(5, cfg.vocab_size, max_input=12,
                                  max_output=6, seed=11)
    tracer = Tracer()
    srv = ChunkedServer(cfg, params, tracer=tracer, **SRV_KW)
    srv.serve(clone_requests(reqs))         # compile warmup
    tracer.clear()
    with jax.transfer_guard("disallow"):
        srv.serve(clone_requests(reqs))
    assert len(tracer.requests) == len(reqs)
    assert request_latency_summary(tracer)["ttft_s"]["count"] == \
        len(reqs)
