"""Model FLOP/s utilisation of training: the local-training operations
of every sensor-round in the window (forward and backward of the
unpadded autoencoder) over the window and the chips' bf16 peak."""
from bench import counts


def read(ctx):
    flops = ctx.counters["sensor_rounds"] * counts.train_flops_per_sensor_round(ctx.cfg)
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (ctx.counters["window_s"] * peak)
