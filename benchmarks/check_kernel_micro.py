"""Perf-trend gate for the kernel microbenchmark.

  python -m benchmarks.check_kernel_micro FRESH.json BASELINE.json

Compares a freshly generated ``kernel_micro`` JSON against the committed
baseline (``experiments/bench/kernel_micro.json``) and exits non-zero when
any jnp-ref row regressed by more than THRESHOLD (default 3x — generous on
purpose: shared CI runners are noisy, and the gate exists to catch
*structural* regressions such as an accidentally de-jitted hot path, not
scheduling jitter).  Checked per matching row: ``us_fused_ref`` and
``us_wire_ref`` in the fused-aggregate table and ``us_fused_ref`` in the
local-train table.

``compare``/``gate_main`` are table-agnostic so sibling gates (e.g.
``benchmarks/check_serve_bench``) reuse them with their own row specs.
"""
from __future__ import annotations

import argparse
import json
import sys

THRESHOLD = 3.0

# (table name, row-key fields, timed field) triples this gate checks.
CHECKS = (
    ("agg_rows", ("n_clients", "d"), "us_fused_ref"),
    ("agg_rows", ("n_clients", "d"), "us_wire_ref"),
    ("local_train_rows", ("n_clients", "window"), "us_fused_ref"),
)


def _index(rows: list[dict], keys: tuple[str, ...]) -> dict:
    return {tuple(r[k] for k in keys): r for r in rows}


def compare(
    fresh: dict, baseline: dict, threshold: float, checks=CHECKS, unit="us"
) -> list[str]:
    failures = []
    for table, keys, field in checks:
        fresh_rows = _index(fresh.get(table, []), keys)
        for row_key, base_row in _index(baseline.get(table, []), keys).items():
            if field not in base_row:
                continue  # baseline predates this metric: no trend yet
            tag = f"{table}[{dict(zip(keys, row_key))}].{field}"
            fresh_row = fresh_rows.get(row_key)
            if fresh_row is None or field not in fresh_row:
                # A vanished cell must fail loudly, or a benchmark refactor
                # that drops rows silently disables the very gate meant to
                # catch structural regressions.
                failures.append(f"{tag}: missing from the fresh JSON")
                continue
            ratio = fresh_row[field] / max(base_row[field], 1e-9)
            line = (
                f"{tag}: {base_row[field]:.2f}{unit} -> "
                f"{fresh_row[field]:.2f}{unit} ({ratio:.2f}x)"
            )
            if ratio > threshold:
                failures.append(line)
            else:
                print(f"ok   {line}")
    return failures


def gate_main(checks=CHECKS, name: str = "kernel_micro") -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("fresh", help=f"freshly generated {name}.json")
    ap.add_argument("baseline", help=f"committed baseline {name}.json")
    ap.add_argument("--threshold", type=float, default=THRESHOLD)
    args = ap.parse_args()
    with open(args.fresh) as f:
        fresh = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)
    failures = compare(fresh, baseline, args.threshold, checks)
    if failures:
        print(f"PERF REGRESSION (> {args.threshold}x):")
        for line in failures:
            print(f"FAIL {line}")
        print(
            "If this PR intentionally changed the benchmark or the runner "
            "hardware class changed, regenerate the baseline: "
            f"PYTHONPATH=src python -m benchmarks.run --only {name}"
        )
        return 1
    print(f"{name} within {args.threshold}x of the committed baseline")
    return 0


def main() -> int:
    return gate_main()


if __name__ == "__main__":
    sys.exit(main())
