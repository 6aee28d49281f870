"""Benchmark driver: one module per paper table/figure + ours.

  PYTHONPATH=src python -m benchmarks.run [--only NAME[,NAME]] [--full]

quick (default): geometry/energy studies at PAPER scale, training studies
at the CPU budget.  --full: everything at the paper's exact scale.
Results land in experiments/bench/<name>.json; a human table prints per
module.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import common
from repro.launch import compile_cache

MODULES = (
    "fig4_convergence",
    "fig5_participation",
    "table3_scalability",
    "fig6_energy",
    "fig7_noniid",
    "table4_real",
    "ablations",
    "kernel_micro",
    "serve_bench",
    "load_bench",
    "roofline",
    "async_bench",
    "robustness_bench",
    "drift_bench",
    "scale_bench",
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale training studies (slow on CPU)")
    args = ap.parse_args()
    compile_cache.enable()

    scale = common.Scale(quick=not args.full)
    names = args.only.split(",") if args.only else list(MODULES)
    unknown = [n for n in names if n not in MODULES]
    if unknown:
        # Fail fast with the valid choices instead of letting __import__
        # raise a raw ModuleNotFoundError mid-suite on a typo'd --only.
        ap.error(
            f"unknown benchmark module(s): {', '.join(unknown)} "
            f"(choose from: {', '.join(MODULES)})"
        )
    failures = []
    for name in names:
        mod = __import__(f"benchmarks.{name}", fromlist=["run", "report"])
        t0 = time.time()
        try:
            res = mod.run(scale)
        except Exception as e:  # keep the suite going; report at the end
            failures.append((name, repr(e)))
            print(f"[FAIL] {name}: {e!r}", flush=True)
            continue
        wall = time.time() - t0
        path = common.save_result(name, res)
        print("=" * 72)
        print(mod.report(res))
        print(f"[{name}: {wall:.1f}s -> {path}]", flush=True)

    print("=" * 72)
    if failures:
        print(f"{len(failures)} benchmark module(s) failed: {failures}")
        sys.exit(1)
    print(f"all {len(names)} benchmark modules completed")


if __name__ == "__main__":
    main()
