"""90th percentile of the wait from a request's scheduled arrival to
its admission into a slot (the program's Tracer: admit minus enqueue),
over every request sent; one never admitted counts as infinitely late."""

import numpy as np


def read(ctx):
    waits = []
    for it in ctx.items:
        rec = ctx.records.get(it.rid)
        q = None if rec is None else rec.queue_delay_s
        waits.append(float("inf") if q is None else q)
    return float(np.percentile(waits, 90)) * 1e3 if waits else None
