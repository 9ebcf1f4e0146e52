"""Share of the profiled window in which no operation ran on the
device: 1 minus the union of the ``XLA Ops`` intervals over the
window."""

from bench import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    bw = trace_reduce.busy_and_window(ctx.trace)
    if not bw or bw[1] <= 0:
        return None
    return 100.0 * (1.0 - bw[0] / bw[1])
