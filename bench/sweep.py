"""Offered-rate sweep of an open-loop serving cell, on the chip.

    python3 bench/sweep.py --workload serve-paper-latency --rates 500,1000,2000 [--seconds 10]

Runs the cell's window once per rate, each with a fresh service, and
prints one JSON line per rate: the latency percentiles, how late the
generator ran (the last request's lateness shows a growing backlog) and
how many requests were left unanswered.  The highest
rate whose p95 stays under the cell's latency limit (``p95_limit_ms`` in
its traffic file) is what the cell's fixed rate is set from, at 0.8 of
it.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    from bench import device, spec
    from bench.drivers import serve
    from repro.launch import compile_cache

    cell = spec.cell(args.workload)
    device.require(cell["chips"])
    compile_cache.enable()
    limit = cell["traffic"].get("p95_limit_ms")
    for rate in (float(r) for r in args.rates.split(",")):
        c = dict(cell, traffic=dict(cell["traffic"], rate_hz=rate))
        drv = serve.Driver(c, args.seed, jax.profiler.TraceAnnotation)
        drv.setup()
        gc.collect()
        gc.freeze()
        drv.window(args.seconds)
        e2e = drv.end_to_end()
        attempted, failed = drv.attempted_failed()
        counters = drv.counters()
        # Sustained: every request answered, the tail under the limit, and
        # no backlog left growing at the close of the window.
        line = {"rate_hz": rate, **e2e, **counters, "attempted": attempted,
                "failed": failed,
                "sustained": failed == 0 and limit is not None
                and e2e["serve_p95_ms"] <= limit
                and counters["generator_late_last_ms"] <= limit}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
