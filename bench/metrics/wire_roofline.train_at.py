"""Roofline share of the wire kernels (emit, then scatter-accumulate into
the fog buffers) at the Anomaly Transformer's d: the algorithm's bytes
(``bench/counts_at.wire_bytes``) over the kernels' device time.  A few
operations per byte, so the bandwidth bounds it."""
from bench import counts_at, trace

KERNELS = {"compress_wire_blocks", "wire_aggregate_blocks"}


def read(ctx):
    t = trace.kernel_ns(ctx.events, ctx.window_ns, KERNELS, ctx.kernels) / 1e9
    rounds = ctx.counters["jobs"] * ctx.traffic["seeds_per_job"] * ctx.cfg["rounds"]
    nbytes = counts_at.wire_bytes(ctx.cfg, ctx.counters["sensor_rounds"], rounds)
    return trace.roofline_percent(0.0, nbytes, t, ctx.peaks, ctx.chips)
