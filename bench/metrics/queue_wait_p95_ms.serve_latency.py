"""95th percentile of the time a request waited in the queue before the
micro-batch that finished it started (the program's
``serve.queue_wait_s`` counter, one value per completed request), in
milliseconds."""
from bench import program


def read(ctx):
    s = program.stats("serve.queue_wait_s")
    return None if s is None else s["p95"] * 1e3
