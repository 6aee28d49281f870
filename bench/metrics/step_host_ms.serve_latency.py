"""Mean host time of one ScoringService.step in the window: the length
of the benchmark's ``bench.step`` span less the device time inside it,
in milliseconds."""
from bench import trace


def read(ctx):
    planes = trace.device_planes(ctx.events)
    steps = [s for s in trace.spans(ctx.events, "bench.step")
             if ctx.window_ns[0] <= s.start_ns <= ctx.window_ns[1]]
    if not steps or not planes:
        return None
    ops = [(o.start_ns, o.end_ns) for o in trace.ops(ctx.events, planes[0])]
    host = 0.0
    for s in steps:
        busy = sum(e - b for b, e in trace.union(ops, s.start_ns, s.end_ns))
        host += s.dur_ns - busy
    return host / len(steps) / 1e6
