"""Device time per sensor-round outside the two wire kernels: local
training of the Anomaly Transformer, with the round's physics, global
step and evaluation beside it, in microseconds."""
from bench import trace

WIRE = {"compress_wire_blocks", "wire_aggregate_blocks"}


def read(ctx):
    if not trace.device_planes(ctx.events):
        return None
    other = (trace.busy_ns(ctx.events, ctx.window_ns)
             - trace.kernel_ns(ctx.events, ctx.window_ns, WIRE, ctx.kernels))
    return other / 1e3 / ctx.counters["sensor_rounds"]
