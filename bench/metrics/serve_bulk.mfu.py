"""Model FLOP/s utilisation of bulk scoring: the forward and error
operations of the rows returned in the window over the window and the
chips' bf16 peak."""
from bench import counts


def read(ctx):
    flops = ctx.counters["rows_in_window"] * counts.score_flops_per_row(ctx.cfg)
    return 100.0 * flops / (ctx.counters["window_s"] * ctx.chips * ctx.peaks["bf16_flops_per_s"])
