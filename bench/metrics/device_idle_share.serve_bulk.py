"""Share of the bulk-scoring window (closed-loop serving) in which the device ran nothing."""
from bench import trace


def read(ctx):
    return trace.idle_percent(ctx.events, ctx.window_ns)
