"""Mean wall time of a decode step that emitted a token: over every
request of the run, the Tracer's ``decode_s`` summed over its
``decode_steps`` summed.  A decode piggybacked on a chunk step counts
that step's wall; a span's wall is split evenly over its steps.
Silent where the program keeps no ``decode_s``."""


def read(ctx):
    steps = secs = 0
    for it in ctx.items:
        rec = ctx.records.get(it.rid)
        steps += getattr(rec, "decode_steps", 0)
        secs += getattr(rec, "decode_s", 0.0)
    return secs / steps * 1e3 if steps else None
