"""90th percentile, over every request sent, of how late the program's
scheduler saw a request: from its scheduled arrival to its release into
the admission queue (the Tracer's ``t_seen`` minus ``t_enqueue``).  One
never seen counts as infinitely late.  It logs the count and the 90th
percentile for each kind of dispatch the scheduler ran last before it
saw the request (``seen_after``).  Silent where the program keeps no
``t_seen``."""

import collections

import numpy as np


def p90(xs):
    # interpolating between two unseen requests gives inf - inf
    with np.errstate(invalid="ignore"):
        v = float(np.percentile(xs, 90))
    return float("inf") if np.isnan(v) else v


def read(ctx):
    late, by = [], collections.defaultdict(list)
    seen = False
    for it in ctx.items:
        rec = ctx.records.get(it.rid)
        t = getattr(rec, "t_seen", None)
        if t is None or rec.t_enqueue is None:
            x, kind = float("inf"), "unseen"
        else:
            x, kind, seen = t - rec.t_enqueue, str(rec.seen_after), True
        late.append(x)
        by[kind].append(x)
    if not seen:
        return None
    ctx.note("arrival_late_p90_ms: " + ", ".join(
        f"after {k}: n={len(v)} p90 {p90(v) * 1e3:.2f} ms"
        for k, v in sorted(by.items())))
    return p90(late) * 1e3
