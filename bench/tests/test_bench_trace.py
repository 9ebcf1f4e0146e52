"""The reduction from a profiler trace to busy time, per-program time,
top ops and idle gaps: on hand-made events, and on a small trace
recorded on a TPU v5e (bench/tests/data/small_trace.xplane.pb: three
host spans ``bench.step``, each around one execution of a matmul
program and one of an elementwise program)."""

import os

import pytest

import tiny_cell  # noqa: F401
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_trace.xplane.pb")


def _trace():
    ev = tr.Event
    ops = {"/device:TPU:0": [ev("a", 0, 10), ev("b", 5, 20), ev("a", 40, 50),
                             ev("c", 70, 75)]}
    mods = {"/device:TPU:0": [ev("jit__span_impl(1)", 0, 20),
                              ev("jit__chunk_impl(2)", 40, 50),
                              ev("jit__span_impl(1)", 70, 75)]}
    host = [ev("bench.decode_span", 0, 30), ev("bench.admit", 30, 38),
            ev("bench.chunk_step", 38, 60), ev("bench.decode_span", 65, 80),
            ev("bench.serve", 0, 100)]
    return tr.Trace(ops, mods, host)


def test_union_window_and_busy():
    assert tr.merge([(5, 20), (0, 10), (40, 50)]) == [(0, 20), (40, 50)]
    assert tr.clip([(0, 20), (40, 50)], 10, 45) == [(10, 20), (40, 45)]
    t = _trace()
    assert tr.window(t) == (0, 100)
    busy, win = tr.busy_and_window(t)
    assert busy == pytest.approx(35e-9) and win == pytest.approx(100e-9)


def test_program_time_by_host_span():
    t = _trace()
    spans = [e for e in t.host if e.name == "bench.decode_span"]
    per, names = tr.program_time(t, spans, "_span_impl")
    assert per == pytest.approx([20e-9, 5e-9])
    assert names == ["jit__span_impl(1)"]
    assert tr.program_time(t, spans[:1], "_span_impl")[0] is None


def test_top_ops_and_gaps():
    t = _trace()
    assert tr.top_ops(t, 2) == [["a", pytest.approx(20e-9)],
                                ["b", pytest.approx(15e-9)]]
    gaps = tr.idle_gaps(t, 3)
    # gaps: 20-40 (admit covers 30..38 at the middle 30), 50-70, 75-100
    assert [g[1] for g in gaps] == pytest.approx([25e-9, 20e-9, 20e-9])
    assert gaps[0][0] == "bench.serve"
    assert {g[0] for g in gaps} <= {"bench.serve", "bench.admit",
                                    "bench.chunk_step"}


def test_recorded_tpu_trace():
    t = tr.load(DATA)
    assert len(t.ops) == 1 and next(iter(t.ops)).startswith("/device:TPU:")
    steps = [e for e in t.host if e.name == "bench.step"]
    assert len(steps) == 3
    busy, win = tr.busy_and_window(t)
    assert 0 < busy < win
    # each step ran one matmul program and one elementwise program
    assert tr.program_time(t, steps, "jit_")[0] is None
    per, names = tr.program_time(t, steps, "5909886453843021046")
    assert len(per) == 3 and len(names) == 1
    assert 0 < sum(per) <= busy
    assert tr.top_ops(t)[0][1] > 0
    assert len(tr.idle_gaps(t)) >= 3
