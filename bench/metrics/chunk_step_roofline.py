"""Roofline share of the chunk-step program: the least time its work
needs on this chip (bench/work.py: every weight once, the live KV of
each slot's rows, the new KV; model FLOPs of the rows), over its device
time in the trace.  Dispatches are paired in order with the executions
of the program named ``*_chunk_impl*`` through the benchmark's host
spans around each dispatch."""

from bench import trace_reduce, work


def read(ctx):
    if ctx.trace is None:
        return None
    spans = sorted((e for e in ctx.trace.host
                    if e.name == "bench.chunk_step"), key=lambda e: e.start)
    disp = [d for d in ctx.drv.dispatches if d.kind == "chunk"]
    if not spans or len(spans) != len(disp):
        return None
    per, names = trace_reduce.program_time(ctx.trace, spans, "_chunk_impl")
    if per is None or sum(per) <= 0:
        return None
    least = sum(work.least_time(d.flops, d.nbytes, ctx.peaks) for d in disp)
    ctx.note(f"chunk_step_roofline: {len(per)} executions of {names}")
    return 100.0 * least / sum(per)
