"""Model FLOPs of every token the chunk steps and decode spans
processed inside the profiled window (bench/work.py: 2 x the layers'
parameters per token, attention over the live context, the head for
each token picked), over the window's length times the chip's peak.
Silent where the profiler saw no device."""


def read(ctx):
    b = ctx.drv.trace_bounds
    if ctx.trace is None or not b or b[1] is None or not ctx.drv.dispatches:
        return None
    flops = sum(d.flops for d in ctx.traced("chunk") + ctx.traced("span"))
    return 100.0 * flops / ((b[1] - b[0]) * ctx.peaks["bf16_flops"])
