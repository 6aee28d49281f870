"""Roofline share of compression and fog aggregation, whichever kernels
implement it (dense fused, or sparse wire emit plus wire aggregate): the
algorithm's bytes over their device time.  Its operations are a few per
byte, so the bandwidth bounds it."""
from bench import counts, trace

KERNELS = {"compress_aggregate_blocks", "compress_wire_blocks", "wire_aggregate_blocks"}


def read(ctx):
    t = trace.kernel_ns(ctx.events, ctx.window_ns, KERNELS, ctx.kernels) / 1e9
    rounds = ctx.counters["jobs"] * ctx.traffic["seeds_per_job"] * ctx.cfg["rounds"]
    nbytes = counts.aggregate_bytes(ctx.cfg, ctx.counters["sensor_rounds"], rounds)
    return trace.roofline_percent(0.0, nbytes, t, ctx.peaks, ctx.chips)
