"""Roofline share of the fused score kernel: unpadded bytes and
operations of the rows it scored in the window over its device time."""
from bench import counts, trace

KERNELS = {"score_blocks"}


def read(ctx):
    t = trace.kernel_ns(ctx.events, ctx.window_ns, KERNELS, ctx.kernels) / 1e9
    rows, calls = ctx.counters["rows_scored_in_window"], ctx.counters["steps_in_window"]
    return trace.roofline_percent(rows * counts.score_flops_per_row(ctx.cfg),
                                  counts.score_bytes(ctx.cfg, rows, calls), t,
                                  ctx.peaks, ctx.chips)
