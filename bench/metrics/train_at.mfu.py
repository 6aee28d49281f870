"""Model FLOP/s utilisation of Anomaly Transformer training: forward and
backward operations of every window trained in the window (published,
unpadded shapes; ``bench/counts_at.py``) over the window and the chips'
bf16 peak.  The program's own count of windows a sensor trains in a round
(``engine.local_windows``) must agree with the benchmark's."""
from bench import counts_at, program


def read(ctx):
    want = counts_at.windows_per_sensor_round(ctx.cfg)
    got = program.stats("engine.local_windows")
    if got is not None and got["mean"] != want:
        raise ValueError(f"the program trains {got['mean']} windows a sensor-round, "
                         f"the benchmark counts {want}")
    flops = ctx.counters["sensor_rounds"] * counts_at.train_flops_per_sensor_round(ctx.cfg)
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (ctx.counters["window_s"] * peak)
