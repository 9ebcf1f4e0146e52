"""Mean host time between dispatches in the profiled window: from the
end of a dispatch's ``serve.*.wait`` span (``block_until_ready``) to
the start of the next dispatch's ``serve.*.enqueue`` span, over
consecutive pairs, from the program's own spans.  While no dispatch
overlaps another the device idles through each such gap.  It logs each
span's mean self time per ``serve.step``, the gaps' share of the window
beside the device's idle share, and the innermost ``serve.*`` span over
each of the longest idle gaps.  Silent where the program writes no
``serve.*`` span."""

from bench import program_spans, trace_reduce


def read(ctx):
    tr = program_spans.load(ctx)
    gaps = program_spans.dispatch_gaps(tr) if tr is not None else []
    if not gaps:
        return None
    steps = len(program_spans.named(tr, "serve.step"))
    own = sorted(program_spans.self_ns(tr).items(), key=lambda x: -x[1])
    ctx.note(f"host_gap_ms: {len(gaps)} gaps; self ms per serve.step "
             f"({steps}): " + ", ".join(f"{n} {ns * 1e-6 / max(steps, 1):.3f}"
                                        for n, ns in own))
    w = trace_reduce.window(ctx.trace) if ctx.trace is not None else None
    if w is not None and w[1] > w[0]:
        ctx.note(f"host_gap_ms: gaps {sum(gaps) * 1e-6:.3f} ms over a "
                 f"{(w[1] - w[0]) * 1e-6:.3f} ms window = "
                 f"{100 * sum(gaps) / (w[1] - w[0]):.3f}% (device_idle "
                 "reads the same window)")
    if tr.ops:
        for name, secs in trace_reduce.idle_gaps(tr):
            ctx.note(f"host_gap_ms: idle gap {secs * 1e3:.3f} ms in {name}")
    return sum(gaps) / len(gaps) * 1e-6
