"""Device time per job-round outside local training and aggregation:
association, cooperation, energy, evaluation and the glue of the round
loop (core/hfl.py), in milliseconds."""
from bench import trace

KERNELS = {"local_train_blocks", "compress_aggregate_blocks", "compress_wire_blocks",
           "wire_aggregate_blocks"}


def read(ctx):
    if not trace.device_planes(ctx.events):
        return None
    other = (trace.busy_ns(ctx.events, ctx.window_ns)
             - trace.kernel_ns(ctx.events, ctx.window_ns, KERNELS, ctx.kernels))
    return other / 1e6 / (ctx.counters["jobs"] * ctx.cfg["rounds"])
