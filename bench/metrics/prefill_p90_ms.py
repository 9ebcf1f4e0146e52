"""90th percentile, over every request of the run that was prefilled,
of its own prefill time: the sum of the walls (entry to
``block_until_ready``) of the chunk-step dispatches that computed part
of its prompt (the Tracer's ``prefill_s``).  With ``queue_delay_s`` it
splits an offline TTFT into the wait for a slot and the request's own
prefill.  Silent where the program keeps no ``prefill_s``."""

import numpy as np


def read(ctx):
    recs = [ctx.records.get(it.rid) for it in ctx.items]
    recs = [r for r in recs if getattr(r, "prefill_steps", 0)]
    if not recs:
        return None
    secs = [r.prefill_s for r in recs]
    ctx.note(f"prefill_p90_ms: {len(recs)} of {len(ctx.items)} requests; "
             f"median {float(np.median(secs)) * 1e3:.2f} ms over "
             f"{float(np.median([r.prefill_steps for r in recs])):.0f} "
             f"chunk steps")
    return float(np.percentile(secs, 90)) * 1e3
