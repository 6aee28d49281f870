"""Unit tests for the CI bench gates (satellites of PR 6).

The gates run in CI against freshly generated JSONs; these tests pin the
``compare`` contracts themselves on synthetic fixtures — a passing pair,
a regressed pair, and the vanished-row case — so a gate refactor cannot
silently stop failing.  Also covers the ``benchmarks.run --only``
typo handling and the step-summary delta table.
"""
import pytest

from benchmarks import (
    bench_summary,
    check_async_bench,
    check_drift_bench,
    check_kernel_micro,
    check_load_bench,
    check_robustness_bench,
    check_scale_bench,
    check_sweep_compile,
)
from benchmarks import run as bench_run


# ---------------------------------------------------------------------------
# check_kernel_micro.compare (shared by check_serve_bench)
# ---------------------------------------------------------------------------

def _kernel_json(us_fused_ref: float) -> dict:
    return {"agg_rows": [{"n_clients": 8, "d": 1352,
                          "us_fused_ref": us_fused_ref}]}


def test_kernel_gate_passes_within_threshold():
    failures = check_kernel_micro.compare(
        _kernel_json(120.0), _kernel_json(100.0), threshold=3.0
    )
    assert failures == []


def test_kernel_gate_trips_on_regression():
    failures = check_kernel_micro.compare(
        _kernel_json(400.0), _kernel_json(100.0), threshold=3.0
    )
    assert len(failures) == 1
    assert "us_fused_ref" in failures[0]


def test_kernel_gate_fails_loudly_on_missing_row():
    fresh = {"agg_rows": []}  # the refactor dropped the cell
    failures = check_kernel_micro.compare(
        fresh, _kernel_json(100.0), threshold=3.0
    )
    assert failures and "missing" in failures[0]


def test_kernel_gate_skips_baseline_without_metric():
    """A baseline predating the metric is 'no trend yet', not a failure."""
    failures = check_kernel_micro.compare(
        _kernel_json(100.0), {"agg_rows": [{"n_clients": 8, "d": 1352}]},
        threshold=3.0,
    )
    assert failures == []


# ---------------------------------------------------------------------------
# check_sweep_compile.compare
# ---------------------------------------------------------------------------

def _sweep_json(programs: int, cells: int = 8) -> dict:
    return {"engine": {
        "sweep_compiled_programs": programs, "sweep_cells": cells,
    }}


def test_sweep_gate_passes_on_equal_counts():
    assert check_sweep_compile.compare(_sweep_json(1), _sweep_json(1)) == []


def test_sweep_gate_trips_on_per_cell_fallback():
    failures = check_sweep_compile.compare(_sweep_json(8), _sweep_json(1))
    assert failures and "fallback" in failures[0]


def test_sweep_gate_trips_on_shrunk_coverage():
    failures = check_sweep_compile.compare(
        _sweep_json(1, cells=2), _sweep_json(1, cells=8)
    )
    assert failures and "shrank" in failures[0]


def test_sweep_gate_fails_loudly_on_missing_engine_block():
    failures = check_sweep_compile.compare({}, _sweep_json(1))
    assert failures and "missing" in failures[0]


# ---------------------------------------------------------------------------
# check_async_bench.compare
# ---------------------------------------------------------------------------

def _async_json(
    s_per_merge: float = 4.0,
    speedup: float = 1.1,
    f1: float = 0.9,
    sync_s: float = 4.5,
) -> dict:
    return {
        "sync": {"sim_s_per_round": sync_s},
        "rows": [{
            "alpha": 0.5, "buffer_frac": 0.25,
            "sim_s_per_merge": s_per_merge,
            "speedup_vs_sync": speedup,
            "f1_mean": f1,
        }],
    }


def test_async_gate_passes_within_threshold():
    failures = check_async_bench.compare(_async_json(), _async_json())
    assert failures == []


def test_async_gate_trips_on_throughput_regression():
    failures = check_async_bench.compare(
        _async_json(s_per_merge=8.0), _async_json(), threshold=1.25
    )
    assert any("sim_s_per_merge" in f for f in failures)


def test_async_gate_trips_on_shrunk_speedup():
    failures = check_async_bench.compare(
        _async_json(speedup=0.7), _async_json(speedup=1.1), threshold=1.25
    )
    assert any("speedup_vs_sync" in f for f in failures)


def test_async_gate_trips_on_f1_drop():
    failures = check_async_bench.compare(
        _async_json(f1=0.7), _async_json(f1=0.9), f1_tol=0.08
    )
    assert any("f1_mean" in f for f in failures)


def test_async_gate_trips_on_sync_baseline_regression():
    """A latency-model slowdown that hits BOTH paths hides in the speedup
    ratio — the sync row's own ratio check is what catches it."""
    failures = check_async_bench.compare(
        _async_json(sync_s=9.0), _async_json(sync_s=4.5)
    )
    assert any("sync.sim_s_per_round" in f for f in failures)


def test_async_gate_fails_loudly_on_missing_row():
    fresh = {"sync": {"sim_s_per_round": 4.5}, "rows": []}
    failures = check_async_bench.compare(fresh, _async_json())
    assert any("missing" in f for f in failures)


# ---------------------------------------------------------------------------
# check_scale_bench.compare (fleet-axis memory + wall-clock, PR 10)
# ---------------------------------------------------------------------------

def _scale_row(n, chunk, temp=65e6, wall=1.0):
    return {"n": n, "chunk": chunk, "temp_bytes": temp, "wall_s": wall}


def _scale_json(dense_temp=260e6, big_temp=65e6, far_temp=65e6, wall=1.0):
    return {"rows": [
        _scale_row(2000, None, temp=dense_temp),
        _scale_row(2000, 512),
        _scale_row(10000, 512, temp=big_temp, wall=wall),
        _scale_row(50000, 512, temp=far_temp),
    ]}


def test_scale_gate_passes_on_healthy_json():
    assert check_scale_bench.compare(_scale_json(), _scale_json()) == []


def test_scale_gate_trips_on_chunk_pin():
    """The headline acceptance pin: chunked 10k temp creeping back toward
    the dense footprint fails even with a matching baseline — and the pin
    needs no baseline at all."""
    failures = check_scale_bench.compare(
        _scale_json(big_temp=200e6), _scale_json(big_temp=200e6)
    )
    assert any("chunk-pin" in f for f in failures)
    failures = check_scale_bench.compare(_scale_json(big_temp=200e6), None)
    assert any("chunk-pin" in f for f in failures)


def test_scale_gate_trips_on_growing_footprint():
    """Chunked temp spreading with N means the footprint follows the fleet
    again — flatness is fresh-internal, no baseline involved."""
    failures = check_scale_bench.compare(
        _scale_json(far_temp=100e6), _scale_json(far_temp=100e6)
    )
    assert any("growing with the fleet" in f for f in failures)


def test_scale_gate_trips_on_memory_regression_vs_baseline():
    failures = check_scale_bench.compare(
        _scale_json(big_temp=80e6), _scale_json(big_temp=65e6)
    )
    assert any("memory regression" in f for f in failures)


def test_scale_gate_trips_on_wall_clock_regression():
    failures = check_scale_bench.compare(
        _scale_json(wall=4.0), _scale_json(wall=1.0)
    )
    assert any("wall-clock regression" in f for f in failures)


def test_scale_gate_fails_loudly_on_missing_cell():
    fresh = _scale_json()
    fresh["rows"] = [r for r in fresh["rows"] if r["n"] != 50000]
    failures = check_scale_bench.compare(fresh, _scale_json())
    assert any("missing" in f for f in failures)


# ---------------------------------------------------------------------------
# check_robustness_bench.compare
# ---------------------------------------------------------------------------

def _robust_row(robust, byz, er, f1, nonfinite=0.0):
    return {
        "robust": robust, "byz_frac": byz, "erasure": er,
        "f1_mean": f1, "nonfinite_rounds": nonfinite,
    }


def _robust_json(
    clean_f1=0.91,
    mean_byz_f1=0.2,
    trim_f1=0.9,
    med_f1=0.9,
    erased_f1=0.88,
    nonfinite=0.0,
    programs=3,
) -> dict:
    return {
        "n_classes": 3,
        "rows": [
            _robust_row("mean", 0.0, 0.0, clean_f1),
            _robust_row("mean", 0.0, 0.3, erased_f1, nonfinite=nonfinite),
            _robust_row("mean", 0.25, 0.0, mean_byz_f1),
            _robust_row("trimmed", 0.25, 0.0, trim_f1),
            _robust_row("trimmed", 0.25, 0.3, erased_f1),
            _robust_row("median", 0.25, 0.0, med_f1),
        ],
        "engine": {"sweep_compiled_programs": programs, "sweep_cells": 6},
    }


def test_robust_gate_passes_on_healthy_grid():
    failures = check_robustness_bench.compare(_robust_json(), _robust_json())
    assert failures == []


def test_robust_gate_trips_when_robust_rule_drops():
    failures = check_robustness_bench.compare(
        _robust_json(trim_f1=0.5), _robust_json(), f1_tol=0.12
    )
    assert any("trimmed" in f and "dropped" in f for f in failures)
    # ...both fresh-internal and vs the committed baseline.
    failures = check_robustness_bench.compare(
        _robust_json(med_f1=0.7), _robust_json(med_f1=0.9), f1_tol=0.12
    )
    assert any("median" in f for f in failures)


def test_robust_gate_trips_when_mean_stops_collapsing():
    """If the attack no longer hurts the plain mean, the benchmark proves
    nothing — that's a failure, not a success."""
    failures = check_robustness_bench.compare(
        _robust_json(mean_byz_f1=0.85), _robust_json(), degrade_margin=0.25
    )
    assert any("no longer degrades" in f for f in failures)


def test_robust_gate_trips_on_nonfinite_rounds():
    failures = check_robustness_bench.compare(
        _robust_json(nonfinite=2.0), _robust_json()
    )
    assert any("non-finite" in f for f in failures)


def test_robust_gate_trips_on_erasure_cliff():
    failures = check_robustness_bench.compare(
        _robust_json(erased_f1=0.3), _robust_json(), erasure_tol=0.15
    )
    assert any("cliff" in f for f in failures)


def test_robust_gate_trips_on_compile_fallback():
    failures = check_robustness_bench.compare(
        _robust_json(programs=6), _robust_json()
    )
    assert any("batching regressed" in f for f in failures)


def test_robust_gate_fails_loudly_on_missing_row():
    fresh = _robust_json()
    fresh["rows"] = [r for r in fresh["rows"] if r["robust"] != "median"]
    failures = check_robustness_bench.compare(fresh, _robust_json())
    assert any("missing" in f for f in failures)
    # No clean anchor row at all: nothing else is checkable.
    failures = check_robustness_bench.compare(
        {"rows": []}, _robust_json()
    )
    assert any("anchor" in f for f in failures)


# ---------------------------------------------------------------------------
# check_drift_bench.compare
# ---------------------------------------------------------------------------

def _drift_row(cell, f1, part, nonfinite=0.0):
    return {
        "cell": cell, "f1_mean": f1, "participation": part,
        "nonfinite_rounds": nonfinite,
    }


def _drift_json(
    static_part=0.89,
    frozen_part=0.71,
    reassoc_part=0.85,
    reassoc_f1=0.84,
    mean_byz_f1=0.23,
    trim_f1=0.84,
    nonfinite=0.0,
    programs=4,
) -> dict:
    return {
        "n_classes": 4,
        "rows": [
            _drift_row("static", 0.84, static_part),
            _drift_row("frozen", 0.84, frozen_part, nonfinite=nonfinite),
            _drift_row("reassoc", reassoc_f1, reassoc_part),
            _drift_row("clean-mean", 0.84, 1.0),
            _drift_row("adaptive-mean", mean_byz_f1, 1.0),
            _drift_row("adaptive-trimmed", trim_f1, 1.0),
            _drift_row("adaptive-median", 0.84, 1.0),
        ],
        "engine": {"sweep_compiled_programs": programs, "sweep_cells": 7},
    }


def test_drift_gate_passes_on_healthy_grid():
    failures = check_drift_bench.compare(_drift_json(), _drift_json())
    assert failures == []


def test_drift_gate_trips_when_frozen_stops_degrading():
    """If stale association no longer sheds participation under drift,
    the scenario demonstrates nothing — that's a failure."""
    failures = check_drift_bench.compare(
        _drift_json(frozen_part=0.88), _drift_json(), part_margin=0.08
    )
    assert any("no longer degrades" in f for f in failures)


def test_drift_gate_trips_when_reassoc_loses_participation():
    failures = check_drift_bench.compare(
        _drift_json(reassoc_part=0.7), _drift_json(), part_tol=0.06
    )
    assert any("re-association lost" in f for f in failures)


def test_drift_gate_trips_when_drift_corrupts_f1():
    failures = check_drift_bench.compare(
        _drift_json(reassoc_f1=0.6), _drift_json(reassoc_f1=0.6), f1_tol=0.12
    )
    assert any("reassoc" in f and "dropped" in f for f in failures)


def test_drift_gate_trips_when_adaptive_mean_stops_collapsing():
    failures = check_drift_bench.compare(
        _drift_json(mean_byz_f1=0.8), _drift_json(), degrade_margin=0.30
    )
    assert any("no longer collapses" in f for f in failures)


def test_drift_gate_trips_when_robust_rule_drops():
    # ...fresh-internal (vs the clean anchor) and vs the committed baseline.
    failures = check_drift_bench.compare(
        _drift_json(trim_f1=0.5), _drift_json(trim_f1=0.5), f1_tol=0.12
    )
    assert any("adaptive-trimmed" in f for f in failures)
    failures = check_drift_bench.compare(
        _drift_json(reassoc_f1=0.75), _drift_json(reassoc_f1=0.9), f1_tol=0.12
    )
    assert any("baseline" in f for f in failures)


def test_drift_gate_trips_on_nonfinite_rounds():
    failures = check_drift_bench.compare(
        _drift_json(nonfinite=1.0), _drift_json()
    )
    assert any("non-finite" in f for f in failures)


def test_drift_gate_trips_on_compile_fallback():
    failures = check_drift_bench.compare(
        _drift_json(programs=7), _drift_json()
    )
    assert any("batching regressed" in f for f in failures)


def test_drift_gate_fails_loudly_on_missing_row():
    fresh = _drift_json()
    fresh["rows"] = [r for r in fresh["rows"] if r["cell"] != "frozen"]
    failures = check_drift_bench.compare(fresh, _drift_json())
    assert any("missing" in f for f in failures)
    # No anchors at all: nothing else is checkable.
    failures = check_drift_bench.compare({"rows": []}, _drift_json())
    assert any("anchor" in f for f in failures)


# ---------------------------------------------------------------------------
# check_load_bench (latency/throughput trends + exact pins + structure)
# ---------------------------------------------------------------------------

def _load_row(trace, config, p50=10.0, p99=20.0, sps=1e6, buckets=(128, 1024),
              completed=100):
    return {
        "trace": trace, "config": config, "n_events": 100,
        "completed": completed, "e2e_p50_ms": p50, "e2e_p99_ms": p99,
        "samples_per_s": sps,
        # json round-trips int keys as strings: model that worst case.
        "compiles_by_bucket": {str(b): 1 for b in buckets},
    }


def _load_json(
    fixed_p99=400.0,
    bucketed_p99=20.0,
    bucketed_sps=1e6,
    compiles=1,
    completed=100,
    mismatch_frac=0.001,
    swap_isolated=True,
) -> dict:
    rows = [
        _load_row("mmpp", "fixed", p99=fixed_p99, buckets=(1024,)),
        _load_row("mmpp", "adaptive_bucketed", p99=bucketed_p99,
                  sps=bucketed_sps, completed=completed),
    ]
    rows[1]["compiles_by_bucket"] = {"128": compiles, "1024": compiles}
    return {
        "replays": rows,
        "int8_parity": {"flag_mismatch_frac": mismatch_frac},
        "tenancy": {
            "compiles_by_bucket": {"128": 1, "1024": 1},
            "swap_isolated": swap_isolated,
            "loaded_step": {"a": 1, "b": 2},
        },
    }


def test_load_gate_passes_on_healthy_json(capsys):
    base = _load_json()
    failures = check_load_bench.compare(
        base, base, 3.0, check_load_bench.LATENCY_CHECKS, unit="ms"
    )
    failures += check_load_bench.compare_throughput(base, base, 3.0)
    failures += check_load_bench.check_exact(base, base)
    failures += check_load_bench.check_structure(base)
    assert failures == []


def test_load_gate_trips_on_latency_regression():
    failures = check_load_bench.compare(
        _load_json(bucketed_p99=90.0), _load_json(), 3.0,
        check_load_bench.LATENCY_CHECKS,
    )
    assert any("e2e_p99_ms" in f for f in failures)


def test_load_gate_trips_on_throughput_drop_inverse_direction():
    """samples_per_s gates the INVERSE ratio: a drop fails, a gain never."""
    failures = check_load_bench.compare_throughput(
        _load_json(bucketed_sps=1e5), _load_json(bucketed_sps=1e6), 3.0
    )
    assert any("samples_per_s" in f for f in failures)
    assert check_load_bench.compare_throughput(
        _load_json(bucketed_sps=1e7), _load_json(bucketed_sps=1e6), 3.0
    ) == []


def test_load_gate_fails_loudly_on_missing_row():
    fresh = _load_json()
    fresh["replays"] = fresh["replays"][:1]    # dropped adaptive_bucketed
    failures = check_load_bench.compare(
        fresh, _load_json(), 3.0, check_load_bench.LATENCY_CHECKS
    )
    assert any("missing" in f for f in failures)
    failures = check_load_bench.check_exact(fresh, _load_json())
    assert any("missing" in f for f in failures)


def test_load_gate_trips_on_retrace_and_dropped_requests():
    failures = check_load_bench.check_exact(
        _load_json(compiles=2), _load_json()
    )
    assert any("compiles_by_bucket" in f for f in failures)
    failures = check_load_bench.check_exact(
        _load_json(completed=99), _load_json(completed=99)
    )
    assert any("completed" in f for f in failures)


def test_load_gate_structure_checks():
    # Adaptive batching failing to beat fixed p99 on the bursty trace is a
    # failure even when every trend ratio looks fine.
    failures = check_load_bench.check_structure(
        _load_json(fixed_p99=15.0, bucketed_p99=20.0)
    )
    assert any("does not beat" in f for f in failures)
    failures = check_load_bench.check_structure(_load_json(mismatch_frac=0.5))
    assert any("int8" in f for f in failures)
    failures = check_load_bench.check_structure({"replays": []})
    assert any("missing" in f for f in failures)


def test_load_gate_trips_on_tenancy_violations():
    bad = _load_json()
    bad["tenancy"]["compiles_by_bucket"] = {"128": 2, "1024": 1}
    failures = check_load_bench.check_exact(bad, _load_json())
    assert any("per bucket" in f for f in failures)
    failures = check_load_bench.check_exact(
        _load_json(swap_isolated=False), _load_json()
    )
    assert any("hot-swap" in f for f in failures)


# ---------------------------------------------------------------------------
# benchmarks.run --only validation + step-summary table
# ---------------------------------------------------------------------------

def test_run_only_rejects_typo_with_usage(monkeypatch, capsys):
    monkeypatch.setattr(
        "sys.argv", ["run.py", "--only", "async_bnech"]
    )
    with pytest.raises(SystemExit) as exc:
        bench_run.main()
    assert exc.value.code == 2  # argparse usage error, not a traceback
    err = capsys.readouterr().err
    assert "unknown benchmark module" in err
    assert "async_bench" in err  # the valid choices are listed


def test_bench_summary_builds_delta_rows(tmp_path):
    import json

    fresh_dir = tmp_path / "fresh"
    base_dir = tmp_path / "base"
    fresh_dir.mkdir()
    base_dir.mkdir()
    (base_dir / "async_bench.json").write_text(json.dumps(_async_json()))
    (fresh_dir / "async_bench.json").write_text(
        json.dumps(_async_json(s_per_merge=4.4))
    )
    rows = bench_summary.delta_rows(str(fresh_dir), str(base_dir))
    tagged = [r for r in rows if r[0] == "async_bench" and r[2] == "sim_s_per_merge"]
    assert tagged, f"no async delta rows in {rows}"
    md = bench_summary.markdown(rows)
    assert "|" in md and "sim_s_per_merge" in md
