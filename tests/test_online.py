"""Online-serving observatory: open-loop arrivals (runtime/arrivals),
``ChunkedServer.serve_online``, windowed telemetry (obs/windows), and
SLO/goodput accounting with the rate sweep (obs/slo).

The load-bearing contract: ``serve_online`` on a closed stream (every
request at t=0) is a *free refactor* of ``serve`` — same admission
order, bit-identical greedy outputs, same compiled programs — and an
open-loop Poisson run charges queue delay from the request's
*scheduled arrival*, not from when the scheduler observed it, while
staying inside the transfer-free contract
(``jax.transfer_guard('disallow')``).
"""

import math

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import api
from repro.obs import (SLOSpec, Tracer, attainment, goodput,
                       max_sustainable_rate, percentiles,
                       request_latency_summary, request_met,
                       slo_report, window_series, window_summary,
                       write_chrome_trace)
from repro.runtime.arrivals import (closed_stream, offered_rate,
                                    poisson_stream, trace_stream)
from repro.runtime.server import (ChunkedServer, clone_requests,
                                  sharegpt_like_requests,
                                  sysprompt_sharegpt_requests)

# ----------------------------------------------------------------------
# arrival streams (pure host-side math)
# ----------------------------------------------------------------------


def _reqs(n=5, seed=0):
    return sharegpt_like_requests(n, 512, max_input=12, max_output=6,
                                  seed=seed)


def test_poisson_stream_is_deterministic_and_sorted():
    reqs = _reqs(8)
    a = poisson_stream(reqs, rate=4.0, seed=7)
    b = poisson_stream(clone_requests(reqs), rate=4.0, seed=7)
    assert [tr.t_arrival for tr in a] == [tr.t_arrival for tr in b]
    ts = [tr.t_arrival for tr in a]
    assert ts == sorted(ts) and all(t > 0 for t in ts)
    assert len(a) == len(reqs)
    # cumsum of positive gaps keeps the original request order
    assert [tr.request.rid for tr in a] == [r.rid for r in reqs]
    # a different seed is different traffic
    c = poisson_stream(reqs, rate=4.0, seed=8)
    assert [tr.t_arrival for tr in c] != ts


def test_poisson_first_arrival_and_offered_rate_convention():
    """Seeded regression pin of the arrival convention: arrival k at
    cumsum(gaps)[k], first arrival one FULL gap after the epoch (never
    t=0), and offered_rate = n / t_last = n / sum(gaps) — n arrivals
    over exactly the n gaps that produced them."""
    reqs = _reqs(6)
    rate, seed = 2.0, 11
    # reference draw: same generator, same consumption order
    gaps = np.random.default_rng(seed).exponential(1.0 / rate,
                                                   size=len(reqs))
    stream = poisson_stream(reqs, rate=rate, seed=seed)
    ts = [tr.t_arrival for tr in stream]
    assert ts == pytest.approx(list(np.cumsum(gaps)))
    assert gaps[0] > 0 and ts[0] == pytest.approx(gaps[0])
    assert offered_rate(stream) == pytest.approx(
        len(reqs) / float(np.sum(gaps)))


def test_poisson_stream_mean_gap_tracks_rate():
    reqs = _reqs(500)
    stream = poisson_stream(reqs, rate=10.0, seed=0)
    realized = offered_rate(stream)
    assert realized == pytest.approx(10.0, rel=0.2)


def test_poisson_stream_rejects_bad_rates():
    for rate in (0.0, -1.0, float("nan"), float("inf")):
        with pytest.raises(ValueError):
            poisson_stream(_reqs(2), rate)


def test_trace_stream_sorts_and_validates():
    reqs = _reqs(3)
    stream = trace_stream(reqs, [2.0, 0.5, 1.0])
    assert [tr.t_arrival for tr in stream] == [0.5, 1.0, 2.0]
    assert [tr.request.rid for tr in stream] == [reqs[1].rid,
                                                 reqs[2].rid,
                                                 reqs[0].rid]
    with pytest.raises(ValueError):
        trace_stream(reqs, [0.0, 1.0])          # length mismatch
    with pytest.raises(ValueError):
        trace_stream(reqs, [0.0, -1.0, 2.0])    # negative offset
    with pytest.raises(ValueError):
        trace_stream(reqs, [0.0, float("nan"), 2.0])


def test_closed_stream_keeps_request_order_at_t0():
    reqs = _reqs(4)
    stream = closed_stream(reqs)
    assert all(tr.t_arrival == 0.0 for tr in stream)
    assert [tr.request.rid for tr in stream] == [r.rid for r in reqs]
    assert offered_rate(stream) is None         # zero span: not a rate
    assert offered_rate([]) is None


# ----------------------------------------------------------------------
# serve_online against the real engine
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("yi-6b")
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


SRV_KW = dict(batch_slots=3, max_len=64, chunk=8, span=4,
              block_size=8, prefix_cache=True, spec_decode=3)


def test_serve_online_closed_stream_matches_serve(setup):
    cfg, params = setup
    reqs = sharegpt_like_requests(6, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=3)
    srv_a = ChunkedServer(cfg, params, **SRV_KW)
    srv_b = ChunkedServer(cfg, params, **SRV_KW)
    a, b = clone_requests(reqs), clone_requests(reqs)
    closed = srv_a.serve(a)
    online = srv_b.serve_online(closed_stream(b))
    for ra, rb in zip(a, b):
        assert ra.output == rb.output, (ra.rid, ra.output, rb.output)
    assert srv_a.compile_counts() == srv_b.compile_counts()
    assert online["online"] == 1.0
    assert online["requests"] == closed["requests"]
    assert online["tokens"] == closed["tokens"]
    assert online["arrival_span_s"] == 0.0
    assert online["offered_rate_rps"] == 0.0    # unbounded, not a rate
    # all six arrived at t=0 into 3 slots: the queue was observed deep
    assert online["peak_queue_depth"] == 6
    assert online["idle_s"] == 0.0              # closed stream never naps


def test_serve_online_poisson_charges_queue_delay_from_arrival(setup):
    cfg, params = setup
    reqs = sharegpt_like_requests(5, cfg.vocab_size, max_input=12,
                                  max_output=6, seed=5)
    tracer = Tracer()
    srv = ChunkedServer(cfg, params, tracer=tracer, **SRV_KW)
    srv.serve(clone_requests(reqs))             # compile warmup
    tracer.clear()
    run = clone_requests(reqs)
    stream = poisson_stream(run, rate=200.0, seed=1)
    stats = srv.serve_online(stream)
    # same greedy outputs as the closed batch (arrival times only
    # reorder *when* work is admitted, never what is computed)
    ref = clone_requests(reqs)
    ChunkedServer(cfg, params, **SRV_KW).serve(ref)
    for ra, rb in zip(ref, run):
        assert ra.output == rb.output
    assert stats["requests"] == len(reqs)
    assert stats["offered_rate_rps"] > 0
    recs = tracer.request_records()
    assert len(recs) == len(reqs)
    # enqueue stamps are the scheduled arrivals (epoch-anchored), so
    # queue delay is from arrival and never negative
    by_rid = {tr.request.rid: tr.t_arrival for tr in stream}
    t0s = sorted(r.t_enqueue for r in recs)
    arrivals = sorted(by_rid.values())
    gaps = [b - a for a, b in zip(t0s, t0s[1:])]
    ref_gaps = [b - a for a, b in zip(arrivals, arrivals[1:])]
    assert gaps == pytest.approx(ref_gaps, abs=1e-6)
    for r in recs:
        assert r.queue_delay_s is not None and r.queue_delay_s >= 0
        assert r.ttft_s >= r.queue_delay_s


def test_serve_online_warm_wave_is_transfer_free(setup):
    cfg, params = setup
    reqs = sharegpt_like_requests(4, cfg.vocab_size, max_input=12,
                                  max_output=6, seed=9)
    srv = ChunkedServer(cfg, params, **SRV_KW)
    srv.serve(clone_requests(reqs))             # compile warmup
    counts = dict(srv.compile_counts())
    with jax.transfer_guard("disallow"):
        run = clone_requests(reqs)
        stats = srv.serve_online(poisson_stream(run, rate=500.0,
                                                seed=2))
    assert stats["requests"] == len(reqs)
    assert all(r.output for r in run)
    assert dict(srv.compile_counts()) == counts  # O(1) programs held


def test_serve_online_sampled_wave_is_transfer_free(setup):
    """Per-request stochastic sampling rides the SAME compiled
    programs as greedy (the flip is in operand values, not signatures)
    and the device-side threefry draw adds no host round-trip: a
    greedy-warmed server runs a sampled open-loop wave under
    transfer_guard('disallow') with zero recompiles."""
    cfg, params = setup
    reqs = sharegpt_like_requests(4, cfg.vocab_size, max_input=12,
                                  max_output=6, seed=13)
    srv = ChunkedServer(cfg, params, **SRV_KW)
    srv.serve(clone_requests(reqs))             # GREEDY compile warmup
    counts = dict(srv.compile_counts())
    run = clone_requests(reqs)
    for i, r in enumerate(run):
        r.sampling = api.SamplingParams(temperature=0.8, top_k=20,
                                        seed=100 + i)
    with jax.transfer_guard("disallow"):
        stats = srv.serve_online(poisson_stream(run, rate=500.0,
                                                seed=2))
    assert stats["requests"] == len(run)
    assert all(r.output for r in run)
    assert dict(srv.compile_counts()) == counts


# ----------------------------------------------------------------------
# windowed telemetry (deterministic fake clock)
# ----------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _synthetic_trace():
    """Two 1s windows: a served request in window 0 (finishing at
    t=1.4, i.e. window 1), then a queued arrival + stall in window 1."""
    clk = FakeClock()
    tr = Tracer(clock=clk)
    tr.meta.update({"batch_slots": 2, "chunk": 8, "span": 4})
    tr.enqueue(0, 16, 10, t=0.1)
    clk.t = 0.2
    tr.admit(0, 0, 0, False)
    tr.span("chunk_dispatch", 0.25, 0.35, packed_tokens=12,
            n_prefill=1, n_decode=0)
    clk.t = 0.5
    tr.first_token(0)
    tr.span("span_dispatch", 0.6, 0.9, steps=4, n_active=1, emitted=4,
            kv_lens=(16,))
    clk.t = 1.4
    tr.finish(0, 10)
    tr.enqueue(1, 8, 4, t=1.6)
    clk.t = 1.9
    tr.event("stall")
    return tr


def test_window_series_buckets_and_rates():
    ws = window_series(_synthetic_trace(), 1.0)
    assert len(ws) == 2
    w0, w1 = ws
    assert w0["tokens"] == 12 + 4 and w0["tokens_per_s"] == 16.0
    assert w0["dispatches"] == 2
    assert w0["busy_s"] == pytest.approx(0.4)
    assert w0["arrivals"] == 1 and w0["admissions"] == 1
    assert w0["queue_depth_max"] == 1 and w0["queue_depth_end"] == 0
    assert w0["chunk_occupancy"] == pytest.approx(12 / 16)
    assert w0["span_utilization"] == pytest.approx(0.5)
    assert w1["arrivals"] == 1 and w1["admissions"] == 0
    assert w1["queue_depth_end"] == 1 and w1["stalls"] == 1
    assert math.isnan(w1["chunk_occupancy"])     # no dispatches


def test_window_series_latency_keyed_on_finish_time():
    ws = window_series(_synthetic_trace(), 1.0)
    # the request FINISHED at t=1.4 -> its TTFT/TPOT land in window 1
    assert ws[0]["finished"] == 0 and ws[0]["ttft_s"]["count"] == 0
    assert math.isnan(ws[0]["ttft_s"]["p50"])
    assert ws[1]["finished"] == 1
    assert ws[1]["ttft_s"]["p50"] == pytest.approx(0.4)
    assert ws[1]["tpot_s"]["p50"] == pytest.approx(0.9 / 9)


def test_window_summary_and_empty_inputs():
    ws = window_series(_synthetic_trace(), 1.0)
    summ = window_summary(ws)
    assert summ["n_windows"] == 2
    assert summ["tokens_per_s"]["count"] == 2
    assert summ["peak_queue_depth"] == 1 and summ["stalls"] == 1
    empty = window_summary([])
    assert empty["n_windows"] == 0
    assert empty["tokens_per_s"]["count"] == 0
    assert math.isnan(empty["tokens_per_s"]["p99"])
    assert window_series(Tracer(clock=FakeClock()), 1.0) == []
    with pytest.raises(ValueError):
        window_series(_synthetic_trace(), 0.0)


def test_percentiles_empty_is_nan_marked_not_zero():
    p = percentiles([])
    assert p["count"] == 0
    for k in ("p50", "p95", "p99", "mean"):
        assert math.isnan(p[k])


def test_chrome_trace_counter_tracks_skip_nan(tmp_path):
    import json
    path = str(tmp_path / "t.json")
    write_chrome_trace(_synthetic_trace(), path, window_s=1.0)
    doc = json.load(open(path))
    cs = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert {e["name"] for e in cs} >= {"tokens/s", "queue depth"}
    for e in cs:
        for v in e["args"].values():
            assert not (isinstance(v, float) and math.isnan(v))
    # window 1 had no dispatches: its occupancy sample is dropped
    w1 = {e["name"] for e in cs if e["ts"] >= 1e6}
    assert "chunk occupancy" not in w1 and "queue depth" in w1
    # window_s=0 (default) emits no counters
    write_chrome_trace(_synthetic_trace(), path)
    doc = json.load(open(path))
    assert not [e for e in doc["traceEvents"] if e["ph"] == "C"]


# ----------------------------------------------------------------------
# SLO / goodput
# ----------------------------------------------------------------------

def test_slo_spec_validates():
    with pytest.raises(ValueError):
        SLOSpec(ttft_s=0.0, tpot_s=1.0)
    with pytest.raises(ValueError):
        SLOSpec(ttft_s=1.0, tpot_s=-1.0)


def test_request_met_predicate():
    tr = _synthetic_trace()
    (rec, unfinished) = tr.request_records()
    # ttft=0.4, tpot=0.1
    assert request_met(rec, SLOSpec(ttft_s=0.45, tpot_s=0.15)) is True
    assert request_met(rec, SLOSpec(ttft_s=0.3, tpot_s=0.15)) is False
    assert request_met(rec, SLOSpec(ttft_s=0.45, tpot_s=0.05)) is False
    assert request_met(unfinished, SLOSpec(1.0, 1.0)) is None
    # single-token response: only the TTFT deadline applies
    clk = FakeClock()
    t1 = Tracer(clock=clk)
    t1.enqueue(0, 4, 1, t=0.0)
    clk.t = 0.2
    t1.first_token(0)
    t1.finish(0, 1)
    (r1,) = t1.request_records()
    assert r1.tpot_s is None
    assert request_met(r1, SLOSpec(ttft_s=0.3, tpot_s=1e-9)) is True


def test_attainment_and_goodput_accounting():
    tr = _synthetic_trace()
    ok = SLOSpec(ttft_s=0.45, tpot_s=0.15)
    att = attainment(tr, ok)
    # rid 1 never finished: excluded from plain attainment but counted
    # in unfinished and charged as a miss by attainment_strict
    assert att == {"finished": 1, "met": 1, "unfinished": 1,
                   "attainment": 1.0, "attainment_strict": 0.5,
                   "ttft_misses": 0, "tpot_misses": 0}
    tight = SLOSpec(ttft_s=0.3, tpot_s=0.05)
    att2 = attainment(tr, tight)
    assert att2["met"] == 0 and att2["attainment"] == 0.0
    assert att2["attainment_strict"] == 0.0
    assert att2["ttft_misses"] == 1 and att2["tpot_misses"] == 1
    gp = goodput(tr, ok, wall_s=2.0)
    assert gp["good_tokens"] == 10 and gp["goodput_tok_s"] == 5.0
    assert gp["throughput_tok_s"] == 5.0
    gp2 = goodput(tr, tight, wall_s=2.0)
    assert gp2["goodput_tok_s"] == 0.0          # deadline blown:
    assert gp2["throughput_tok_s"] == 5.0       # work done, no good
    with pytest.raises(ValueError):
        goodput(tr, ok, wall_s=0.0)
    rep = slo_report(tr, ok, 2.0)
    assert rep["attainment"] == 1.0 and rep["goodput_tok_s"] == 5.0
    assert rep["attainment_strict"] == 0.5 and rep["unfinished"] == 1
    assert rep["slo_ttft_s"] == 0.45
    # nothing issued at all -> both attainments undefined, not 100%
    empty = attainment(Tracer(clock=FakeClock()), ok)
    assert math.isnan(empty["attainment"])
    assert math.isnan(empty["attainment_strict"])
    # issued-but-nothing-finished: plain attainment has no verdicts
    # (NaN) while strict reports the truth — 0% of issued requests met
    clk = FakeClock()
    stuck = Tracer(clock=clk)
    stuck.enqueue(0, 8, 4, t=0.0)
    drowned = attainment(stuck, ok)
    assert math.isnan(drowned["attainment"])
    assert drowned["attainment_strict"] == 0.0
    assert drowned["unfinished"] == 1 and drowned["finished"] == 0


def test_max_sustainable_rate_finds_the_knee():
    def runner(rate):
        return {"attainment": 1.0 if rate <= 2.0 else 0.5}

    res = max_sustainable_rate(runner, [4.0, 1.0, 2.0],
                               target_attainment=0.9)
    assert res["max_sustainable_rps"] == 2.0
    assert [s["rate_rps"] for s in res["sweep"]] == [1.0, 2.0, 4.0]
    assert [s["attained"] for s in res["sweep"]] == [True, True, False]
    assert res["target_attainment"] == 0.9
    nothing = max_sustainable_rate(lambda r: {"attainment": 0.0}, [1.0])
    assert math.isnan(nothing["max_sustainable_rps"])
    with pytest.raises(ValueError):
        max_sustainable_rate(runner, [])


def test_max_sustainable_rate_nan_attainment_is_a_miss():
    nan = float("nan")

    # all-NaN sweep (server drowned at every rate): NaN knee, every
    # swept rate still present in the trajectory as an explicit miss
    res = max_sustainable_rate(lambda r: {"attainment": nan},
                               [1.0, 2.0, 3.0])
    assert math.isnan(res["max_sustainable_rps"])
    assert [s["rate_rps"] for s in res["sweep"]] == [1.0, 2.0, 3.0]
    assert [s["attained"] for s in res["sweep"]] == [False] * 3

    # NaN in the middle: the drowned rate is a miss, NOT a dropped
    # row, and a higher attaining rate can still move the knee past it
    def runner(rate):
        return {"attainment": nan if rate == 2.0 else 1.0}

    res = max_sustainable_rate(runner, [1.0, 2.0, 3.0])
    assert res["max_sustainable_rps"] == 3.0
    assert [s["attained"] for s in res["sweep"]] == [True, False, True]

    # attainment_strict is preferred over plain attainment when both
    # are present: 2 of 200 finished and met -> NOT sustainable
    res = max_sustainable_rate(
        lambda r: {"attainment": 1.0, "attainment_strict": 0.01},
        [1.0], target_attainment=0.99)
    assert math.isnan(res["max_sustainable_rps"])
    assert res["sweep"][0]["attained"] is False


# ----------------------------------------------------------------------
# a rate sweep through the real engine
# ----------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["sharegpt", "sysprompt"])
def test_rate_sweep_through_the_engine(setup, mix):
    """Poisson streams at 0.5x, 1x and 3x the engine's closed-loop
    completion rate, scored against an SLO calibrated from an unloaded
    wave (2x its p99 TTFT/TPOT, so the sweep means the same on any
    host): every rate serves the closed batch's greedy tokens, every
    request finishes, and each point's attainment, goodput, latency
    percentiles and windows are well formed."""
    cfg, params = setup
    if mix == "sharegpt":
        reqs = sharegpt_like_requests(6, cfg.vocab_size, max_input=16,
                                      max_output=8, seed=3)
    else:
        reqs = sysprompt_sharegpt_requests(
            6, cfg.vocab_size, num_templates=2, template_len=12,
            max_input=20, max_output=6, seed=4)
    tracer = Tracer()
    srv = ChunkedServer(cfg, params, tracer=tracer, **SRV_KW)
    ref = clone_requests(reqs)
    srv.serve(ref)                              # compile + tree warm
    closed = srv.serve(clone_requests(reqs))
    closed_rps = closed["requests"] / closed["seconds"]
    want = [r.output for r in ref]

    tracer.clear()
    cal = clone_requests(reqs)
    cal_stats = srv.serve_online(poisson_stream(cal, 0.5 * closed_rps,
                                                seed=4))
    cal_lat = request_latency_summary(tracer)
    slo = SLOSpec(ttft_s=max(2.0 * cal_lat["ttft_s"]["p99"], 1e-3),
                  tpot_s=max(2.0 * cal_lat["tpot_s"]["p99"], 1e-3))
    window_s = max(cal_stats["seconds"] / 8.0, 0.02)

    def run_at(rate):
        tracer.clear()
        run = clone_requests(reqs)
        stats = srv.serve_online(poisson_stream(run, rate, seed=5))
        assert [r.output for r in run] == want, rate
        rep = slo_report(tracer, slo, stats["seconds"])
        rep.update(request_latency_summary(tracer))
        rep["windows"] = window_summary(window_series(tracer, window_s))
        return rep

    rates = [closed_rps * m for m in (0.5, 1.0, 3.0)]
    knee = max_sustainable_rate(run_at, rates, target_attainment=0.9)
    assert len(knee["sweep"]) == 3
    for point in knee["sweep"]:
        assert point["finished"] == len(reqs) and point["unfinished"] == 0
        assert 0.0 <= point["attainment"] <= 1.0
        assert point["goodput_tok_s"] <= point["throughput_tok_s"] + 1e-9
        for k in ("ttft_s", "tpot_s", "queue_delay_s", "e2e_s"):
            p = point[k]
            assert p["count"] > 0, k
            assert p["p50"] <= p["p95"] <= p["p99"], (k, p)
        assert point["windows"]["n_windows"] > 0
    assert (math.isnan(knee["max_sustainable_rps"])
            or knee["max_sustainable_rps"] in rates)
