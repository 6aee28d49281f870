"""Run one benchmark cell once on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix; ``bench/spec.py`` finds their files.  The
run makes its data and weights on the device from ``--seed``, warms the
cell's programs (set-up, reported as ``setup_s``), measures for
``--seconds`` and then checks what the timed path produced against the
plain reference.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
metrics are its per-layer metrics, read from the trace.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last ``checks``, every number compared beside its limit.
The same numbers are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, the run prints no result and
exits with 2.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
# The TPU runtime writes its logs under /tmp unless told otherwise; a run
# writes nothing outside its checkout and its temporary directory.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class Context:
    """What a per-layer reader (``bench/metrics/<metric>.py``) reads."""

    def __init__(self, events, window_ns, kernels, counters, cell, peaks, chips):
        self.events, self.window_ns, self.kernels = events, window_ns, kernels
        self.counters = counters
        self.cfg, self.traffic, self.peaks, self.chips = (
            cell["config"], cell["traffic"], peaks, chips)


def run(cell: dict, seed: int, seconds: float, traced: bool, devices,
        t_start: float = T0, log=None) -> dict:
    """Set up, measure and check one cell on ``devices``; returns the
    result object (without printing it)."""
    import jax

    from bench import check, device, spec, trace
    from repro.launch import compile_cache

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    ready_s = time.perf_counter() - t_start
    compile_cache.enable()
    span = jax.profiler.TraceAnnotation
    drv = spec.driver(cell["traffic"]["kind"]).Driver(cell, seed, span)
    drv.setup()
    # Set-up's garbage is collected now and its survivors are kept out of
    # the collector's later passes, so no pass over them lands in the window.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"setup: {ready_s:.3f} s to the chip, {setup_s - ready_s:.3f} s of data and warm-up")

    logdir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        if traced:
            with trace.capture(logdir), span("bench.window"):
                drv.window(seconds)
            evs = trace.events(logdir)
        else:
            with span("bench.window"):
                drv.window(seconds)
    finally:
        if logdir:
            shutil.rmtree(logdir, ignore_errors=True)
    desc = device.describe(devices)
    desc["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    attempted, failed = drv.attempted_failed()
    counters = drv.counters()
    log(f"window: {json.dumps(counters)}")

    metrics, breakdown = {}, None
    if traced:
        win = trace.window(evs)
        kernels = trace.kernel_names(drv.hlo_texts())
        ctx = Context(evs, win, kernels, counters, cell, device.peaks(desc["kind"]),
                      len(devices))
        for m in cell["per_layer"]:
            value = spec.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        desc["busy_s"] = trace.busy_ns(evs, win) / 1e9
        desc["window_s"] = (win[1] - win[0]) / 1e9
        breakdown = {"device_ops": trace.top_ops(evs, win, kernels),
                     "idle_gaps": trace.idle_gaps(evs, win)}
    else:
        e2e = drv.end_to_end()
        log(f"end_to_end: {json.dumps(e2e)}")
        for m in cell["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    drv.release()
    ok, checks = check.verdict(drv.check_numbers(), cell["limits"])
    result = {"correct": ok and failed == 0, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": desc}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import device, spec

    try:
        cell = spec.cell(args.workload)
        import repro  # noqa: F401  (the system under test, beside the benchmark)

        devices = device.require(cell["chips"])
    except (ImportError, spec.SpecError, device.NoChip) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
