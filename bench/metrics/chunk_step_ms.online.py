"""Mean wall time of a chunk-step dispatch (host preparation plus the
device step, which ends in block_until_ready) in an open-loop cell:
``serving.wall_s.prefill`` over ``serving.dispatches.prefill``."""


def read(ctx):
    n = ctx.totals.get("serving.dispatches.prefill", 0)
    if ctx.offline or not n:
        return None
    return ctx.totals["serving.wall_s.prefill"] / n * 1e3
