"""Share of the Anomaly Transformer training window in which the device
ran nothing."""
from bench import trace


def read(ctx):
    return trace.idle_percent(ctx.events, ctx.window_ns)
