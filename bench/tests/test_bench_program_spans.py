"""The readers of the program's own spans and records on hand-made
traces: host gaps between dispatches, the chunk step's device time
paired with its enqueue spans, and how late the scheduler saw each
request; each silent where the program left nothing to read."""

import types

import pytest

import tiny_cell  # noqa: F401  (puts the checkout on the path)
from bench import harness, program_spans
from bench import trace_reduce as tr

ev = tr.Event


def _trace(modules=None):
    """Three dispatches: chunk (enqueue 10-12, wait 12-100), chunk
    (130-131, 131-200), span (215-216, 216-300); the device runs each
    program in between and idles from 100 to 131 and 200 to 216."""
    host = [ev("serve.step", 0, 125), ev("serve.chunk.prepare", 2, 8),
            ev("serve.chunk.put", 8, 10), ev("serve.chunk.enqueue", 10, 12),
            ev("serve.chunk.wait", 12, 100),
            ev("serve.chunk.commit", 100, 104),
            ev("serve.harvest", 110, 120),
            ev("serve.step", 125, 210), ev("serve.chunk.prepare", 126, 129),
            ev("serve.chunk.put", 129, 130),
            ev("serve.chunk.enqueue", 130, 131),
            ev("serve.chunk.wait", 131, 200),
            ev("serve.step", 210, 305), ev("serve.span.enqueue", 215, 216),
            ev("serve.span.wait", 216, 300)]
    ops = {"/device:TPU:0": [ev("fusion", 11, 100), ev("fusion", 131, 200),
                             ev("while", 216, 300)]}
    mods = modules or {"/device:TPU:0": [ev("jit__chunk_impl(3)", 11, 100),
                                         ev("jit__chunk_impl(3)", 131, 200),
                                         ev("jit__span_impl(4)", 216, 300)]}
    return tr.Trace(ops, mods, host)


def _ctx(trace=None, records=None, items=()):
    notes = []
    ctx = types.SimpleNamespace(drv=types.SimpleNamespace(trace_dir=None),
                                trace=None, records=records or {},
                                items=list(items), note=notes.append,
                                notes=notes)
    if trace is not None:
        ctx.program_trace = trace
    return ctx


def test_gaps_and_self_times():
    t = _trace()
    assert program_spans.dispatch_gaps(t) == [30, 15]
    own = program_spans.self_ns(t)
    # each step less what its phases cover: 125 - 112, 85 - 74, 95 - 85
    assert own["serve.step"] == 13 + 11 + 10
    assert own["serve.chunk.wait"] == 88 + 69
    assert [e.start for e in program_spans.named(t, "serve.step")] == \
        [0, 125, 210]


def test_host_gap_reader():
    read = harness.metric_reader("host_gap_ms")
    ctx = _ctx(_trace())
    assert read(ctx) == pytest.approx(22.5e-6)
    assert any("idle gap" in n and "serve." in n for n in ctx.notes)
    assert read(_ctx(tr.Trace({}, {}, []))) is None
    assert read(_ctx()) is None            # no profile in the run


def test_chunk_device_reader():
    read = harness.metric_reader("chunk_device_ms")
    assert read(_ctx(_trace())) == pytest.approx((89 + 69) / 2 * 1e-6)
    # an execution the spans do not account for: no pairing
    extra = {"/device:TPU:0": [ev("jit__chunk_impl(3)", 1, 2)]
             + _trace().modules["/device:TPU:0"]}
    assert read(_ctx(_trace(extra))) is None
    # host spans only (the CPU has no device planes)
    assert read(_ctx(tr.Trace({}, {}, _trace().host))) is None
    # a program that writes no serve.* span
    assert read(_ctx(tr.Trace(_trace().ops, _trace().modules, []))) is None


def _rec(t_enqueue, t_seen=None, after=None):
    return types.SimpleNamespace(t_enqueue=t_enqueue, t_seen=t_seen,
                                 seen_after=after)


def test_arrival_late_reader():
    read = harness.metric_reader("arrival_late_p90_ms")
    items = [types.SimpleNamespace(rid=i) for i in range(10)]
    recs = {i: _rec(10.0 * i, 10.0 * i + 0.001 * i,
                    "span" if i % 2 else "chunk") for i in range(10)}
    ctx = _ctx(records=recs, items=items)
    assert read(ctx) == pytest.approx(8.1)
    assert any("after span: n=5" in n for n in ctx.notes)
    # one request never seen counts as infinitely late
    recs[9] = _rec(90.0)
    assert read(_ctx(records=recs, items=items)) == float("inf")
    del recs[8]
    assert read(_ctx(records=recs, items=items)) == float("inf")
    # records that keep no t_seen: silent
    old = {i: types.SimpleNamespace(t_enqueue=1.0) for i in range(10)}
    assert read(_ctx(records=old, items=items)) is None


def test_load_is_cached_on_the_context():
    ctx = _ctx()
    assert program_spans.load(ctx) is None
    ctx.drv.trace_dir = "/nonexistent"
    assert program_spans.load(ctx) is None     # loaded once, kept
