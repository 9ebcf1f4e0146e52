"""Scheduler spans and the host/device split (src/repro/obs +
runtime/server.py): the ``serve.*`` spans a traced ``serve_online``
writes into the profiler's trace (names, nesting, one ``step_num`` per
loop iteration, matching the Tracer's dispatch events), the registry's
``host_s + wait_s == wall_s`` per dispatch, when each request was seen,
and that none of it changes what is served."""

import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import api
from repro.obs import NULL_TRACER, Tracer, gap_summary, phase_summary
from repro.runtime.arrivals import closed_stream, poisson_stream
from repro.runtime.server import (ChunkedServer, Request, clone_requests,
                                  sharegpt_like_requests)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("yi-6b")
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


SRV_KW = dict(batch_slots=3, max_len=64, chunk=8, span=4,
              block_size=8, prefix_cache=True)
KINDS = {"chunk": "prefill", "span": "span", "verify": "verify"}
PHASES = ("prepare", "put", "enqueue", "wait", "commit")


def _spans(trace_dir):
    """(name, start_ns, end_ns, step_num) of every serve.* host span."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    stats = dict(e.stats)
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                stats.get("step_num")))
    return sorted(out, key=lambda x: (x[1], -x[2]))


@pytest.mark.parametrize("spec_k", [0, 3])
def test_serve_online_writes_scheduler_spans(setup, tmp_path, spec_k):
    cfg, params = setup
    reqs = sharegpt_like_requests(5, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=4)
    tracer = Tracer()
    srv = ChunkedServer(cfg, params, tracer=tracer, spec_decode=spec_k,
                        **SRV_KW)
    srv.serve(clone_requests(reqs))             # compile warmup
    tracer.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.serve_online(poisson_stream(clone_requests(reqs), rate=100.0,
                                        seed=3))
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    decode = "verify" if spec_k else "span"
    names = {s[0] for s in spans}
    assert names >= {"serve.step", "serve.poll", "serve.admit",
                     "serve.harvest"}
    assert names >= {f"serve.{k}.{p}" for k in ("chunk", decode)
                     for p in PHASES}
    other = "span" if spec_k else "verify"
    assert not any(n.startswith(f"serve.{other}.") for n in names)

    steps = [s for s in spans if s[0] == "serve.step"]
    # one step span per loop iteration, numbered by the loop counter
    assert [s[3] for s in steps] == list(range(srv._step))
    inner = {}
    for name, a, b, _ in spans:
        if name == "serve.step":
            continue
        (owner,) = [s[3] for s in steps if s[1] <= a and b <= s[2]]
        inner.setdefault(owner, []).append((name, a, b))
    dispatched = {k: set() for k in KINDS}
    for step, evs in inner.items():
        for kind in KINDS:
            mine = [e for e in evs if e[0].startswith(f"serve.{kind}.")]
            if not mine:
                continue
            # one dispatch per step, its phases in order, none nested
            assert [e[0] for e in mine] == [f"serve.{kind}.{p}"
                                            for p in PHASES]
            assert all(x[2] <= y[1] for x, y in zip(mine, mine[1:]))
            dispatched[kind].add(step)
    for kind in KINDS:
        events = {args["step"] for _t, k, args in tracer.events
                  if k == f"{kind}_dispatch"}
        assert events == dispatched[kind]
        assert len(events) == srv.metrics.counter_value(
            f"serving.dispatches.{KINDS[kind]}")


@pytest.mark.parametrize("spec_k", [0, 3])
def test_host_and_wait_split_wall_exactly(setup, spec_k):
    cfg, params = setup
    reqs = sharegpt_like_requests(6, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=3)
    srv = ChunkedServer(cfg, params, spec_decode=spec_k, **SRV_KW)
    srv.serve(reqs)
    m, n = srv.metrics, 0
    for phase in ("prefill", "span", "verify"):
        wall = m.hist(f"serving.wall_s.{phase}")
        if wall is None:
            continue
        host = m.hist(f"serving.host_s.{phase}").samples
        wait = m.hist(f"serving.wait_s.{phase}").samples
        assert len(host) == len(wait) == len(wall.samples) == \
            m.counter_value(f"serving.dispatches.{phase}") > 0
        for h, w, t in zip(host, wait, wall.samples):
            assert h + w == t and h > 0 and w >= 0
        n += wall.count
    assert 0 < n <= srv._step
    gap = m.hist("serving.gap_s")
    assert gap.count == n - 1 and min(gap.samples) >= 0
    phases = phase_summary(m)
    for d in phases.values():
        assert d["host_s"] + d["wait_s"] == pytest.approx(d["wall_s"])
    assert gap_summary(m) == {"count": n - 1, "gap_s": gap.total,
                              "mean_gap_s": gap.mean}


def test_null_tracer_serves_identically(setup):
    """Spans and records change nothing served: the same greedy tokens
    and the same compiled programs with a Tracer as with none."""
    cfg, params = setup
    assert NULL_TRACER.phase("serve.step", step_num=1) is \
        NULL_TRACER.phase("serve.admit")
    reqs = sharegpt_like_requests(6, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=7)
    traced = ChunkedServer(cfg, params, tracer=Tracer(), **SRV_KW)
    plain = ChunkedServer(cfg, params, **SRV_KW)
    a, b = clone_requests(reqs), clone_requests(reqs)
    traced.serve_online(closed_stream(a))
    plain.serve_online(closed_stream(b))
    assert [r.output for r in a] == [r.output for r in b]
    assert traced.compile_counts() == plain.compile_counts()
    assert plain.metrics.hist("serving.host_s.prefill").count > 0


def test_traced_online_wave_is_transfer_free(setup):
    cfg, params = setup
    reqs = sharegpt_like_requests(4, cfg.vocab_size, max_input=12,
                                  max_output=6, seed=9)
    tracer = Tracer()
    srv = ChunkedServer(cfg, params, tracer=tracer, **SRV_KW)
    srv.serve(clone_requests(reqs))             # compile warmup
    tracer.clear()
    with jax.transfer_guard("disallow"):
        srv.serve_online(poisson_stream(clone_requests(reqs), rate=500.0,
                                        seed=2))
    assert len(tracer.requests) == len(reqs)
    assert all(r.prefill_steps > 0 and r.decode_steps == r.n_out - 1
               for r in tracer.request_records())


@pytest.mark.parametrize("spec_k", [0, 3])
def test_requests_record_their_prefill_and_decode_time(setup, spec_k):
    """Each request's own share of the dispatches: the chunk steps that
    computed its prompt and the decode steps that emitted its tokens,
    against an independent count of the same rows."""
    cfg, params = setup
    reqs = sharegpt_like_requests(6, cfg.vocab_size, max_input=40,
                                  max_output=12, seed=11)
    tracer = Tracer()
    srv = ChunkedServer(cfg, params, tracer=tracer, spec_decode=spec_k,
                        **SRV_KW)
    rows = {}                   # rid -> prompt tokens of each chunk step
    chunk = srv._run_chunk_step

    def counted():
        for s, req in enumerate(srv.slot_req):
            if req is not None and srv.mode[s] == "prefill":
                off = int(srv.prompt_off[s])
                rows.setdefault(req.rid, []).append(
                    min(srv.chunk, len(req.prompt) - off))
        return chunk()

    srv._run_chunk_step = counted
    srv.serve_online(poisson_stream(reqs, rate=200.0, seed=6))
    recs = tracer.request_records()
    assert len(recs) == len(reqs) and any(r.decode_steps > 1 for r in recs)
    for r in recs:
        assert r.cached_tokens == 0
        assert sum(rows[r.rid]) == r.n_prompt
        assert r.prefill_steps == len(rows[r.rid])
        assert r.decode_steps == r.n_out - 1
        assert 0 < r.prefill_s <= r.ttft_s - r.queue_delay_s + 1e-9
        assert (r.decode_s > 0) == (r.decode_steps > 0)
        assert r.prefill_s + r.decode_s <= r.e2e_s - r.queue_delay_s + 1e-9
        d = r.to_dict()
        for k in ("prefill_steps", "prefill_s", "decode_steps",
                  "decode_s"):
            assert d[k] == getattr(r, k)
    assert sum(r.prefill_steps for r in recs) >= \
        srv.metrics.counter_value("serving.dispatches.prefill")


def test_null_tracer_keeps_no_step_shares(setup):
    cfg, params = setup
    assert NULL_TRACER.prefill_dispatch(0, 1.0) is None
    assert NULL_TRACER.decode_dispatch(0, 2, 1.0) is None
    srv = ChunkedServer(cfg, params, **SRV_KW)
    srv.serve_online(closed_stream(sharegpt_like_requests(
        3, cfg.vocab_size, max_input=16, max_output=6, seed=12)))
    assert NULL_TRACER.requests == {} and NULL_TRACER.events == []


def test_requests_record_when_they_were_seen(setup):
    cfg, params = setup
    reqs = sharegpt_like_requests(6, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=5)
    tracer = Tracer()
    srv = ChunkedServer(cfg, params, tracer=tracer, **SRV_KW)
    srv.serve(clone_requests(reqs))             # compile warmup
    closed = tracer.request_records()
    assert all(r.t_seen == r.t_enqueue and r.seen_after is None
               for r in closed)
    tracer.clear()
    srv.serve_online(poisson_stream(clone_requests(reqs), rate=150.0,
                                    seed=1))
    recs = tracer.request_records()
    assert len(recs) == len(reqs)
    for r in recs:
        assert r.t_seen >= r.t_enqueue
        assert r.t_admit >= r.t_seen
        assert r.seen_after in (None, "chunk", "span")
        assert 0 <= r.admit_step <= r.first_token_step < srv._step
        d = r.to_dict()
        assert d["t_seen"] == r.t_seen and d["seen_after"] == r.seen_after
        assert d["admit_step"] == r.admit_step
        assert d["first_token_step"] == r.first_token_step
    # the first arrival comes one gap after the epoch, before any step
    first = min(recs, key=lambda r: r.t_enqueue)
    assert first.seen_after is None
    assert any(r.seen_after is not None for r in recs)


def test_prefix_hits_counted_once_when_the_head_stalls(setup):
    """A queue head whose prompt hits the cache but whose reservation
    does not fit is looked up again on every retry; the hit counters
    count it once, when it is admitted."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    p = rng.integers(0, cfg.vocab_size, 33).astype(np.int32)
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=128, chunk=8,
                        span=4, block_size=8, num_blocks=12)
    srv.serve([Request(0, p, max_new=2)])       # caches 4 blocks of p
    other = (p[:30] + 1) % cfg.vocab_size
    tail = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    # B reserves 8 blocks; C needs 7, 4 of them cached: its lookup
    # passes the cheap bound, then pinning the hit leaves too few
    b = Request(1, other, max_new=34)
    c = Request(2, np.concatenate([p[:32], tail]), max_new=8)
    stats = srv.serve([b, c])
    m = srv.metrics
    assert stats["admission_stalls"] >= 1
    assert m.counter_value("serving.prefix.lookups") >= 3
    assert m.counter_value("serving.prefix.hits") == 1
    assert m.counter_value("serving.prefix.hit_tokens") == 32
    assert stats["prefix_hit_requests"] == 1
    assert stats["prefix_cached_tokens"] == 32
    assert b.done and c.done and len(c.output) == 8
