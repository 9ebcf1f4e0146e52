"""Share of admitted prompt tokens served from the radix prefix cache:
the tokens each admission mapped from the cache (the Tracer's admit
record) over the prompt tokens admitted.  The registry's
``serving.prefix.hit_tokens`` counts every lookup, and a queue head that
stalls after its lookup is looked up again, so it can count a hit
twice; the admit record counts each admission once."""


def read(ctx):
    hit = total = 0
    for it in ctx.items:
        rec = ctx.records.get(it.rid)
        if rec is not None and rec.t_admit is not None:
            hit += rec.cached_tokens
            total += rec.n_prompt
    return hit / total if total else None
