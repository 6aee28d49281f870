"""Roofline share of the fused local-train kernel: the least time its
work needs (unpadded operations and bytes of every sensor-round in the
window) over the device time of its events."""
from bench import counts, trace

KERNELS = {"local_train_blocks"}


def read(ctx):
    t = trace.kernel_ns(ctx.events, ctx.window_ns, KERNELS, ctx.kernels) / 1e9
    n = ctx.counters["sensor_rounds"]
    return trace.roofline_percent(
        n * counts.train_flops_per_sensor_round(ctx.cfg),
        n * counts.train_bytes_per_sensor_round(ctx.cfg), t, ctx.peaks, ctx.chips)
