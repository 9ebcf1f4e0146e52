"""Mean device time of one chunk step: the executions of the program
named ``*_chunk_impl*`` in the profile, paired in order with the
program's ``serve.chunk.enqueue`` spans (``trace_reduce.program_time``),
so that the pairing holds whether or not the host waits for each step
before the next.  Silent without device planes, and where the spans
and the executions differ in number."""

from bench import program_spans, trace_reduce


def read(ctx):
    tr = program_spans.load(ctx)
    if tr is None or not tr.modules:
        return None
    spans = program_spans.named(tr, "serve.chunk.enqueue")
    if not spans:
        return None
    per, names = trace_reduce.program_time(tr, spans, "_chunk_impl")
    if not per:
        return None
    ctx.note(f"chunk_device_ms: {len(per)} executions of {names}")
    return sum(per) / len(per) * 1e3
