"""Whole runs of tiny cells on the CPU, past the harness's look for a
chip, with the timed path broken underneath: ``correct`` must come out
false for each fault the cell can have.  (No cell spans chips, so none
can lose the exchange between chips.)  A sound tiny run comes out true."""
import bench_cells
import jax
import jax.numpy as jnp
import pytest

import repro.core.aggregation as agg
import repro.core.anomaly as anomaly
import repro.core.hfl as hfl
import repro.kernels.ops as kops


def state_unchanged(monkeypatch):
    """Every job hands back the parameters it started from."""
    train = hfl.train

    def frozen(key, init_params, *a, **kw):
        _, metrics = train(key, init_params, *a, **kw)
        return init_params, metrics

    monkeypatch.setattr(hfl, "train", frozen)


def half_the_clients(monkeypatch):
    """Fog aggregation leaves out the second half of the clients and takes
    the mean over the rest."""
    acc = agg.compress_and_accumulate

    def half(deltas, err, fog_id, weights, *a, **kw):
        keep = jnp.arange(weights.shape[0]) < weights.shape[0] // 2
        return acc(deltas, err, fog_id, weights * keep, *a, **kw)

    monkeypatch.setattr(agg, "compress_and_accumulate", half)


def f1_altered(monkeypatch):
    """The detector's F1 is altered where it is produced."""
    f1 = anomaly.pointwise_f1

    def off(pred, label):
        r = f1(pred, label)
        return r._replace(f1=r.f1 + 0.05)

    monkeypatch.setattr(anomaly, "pointwise_f1", off)


def error_altered(monkeypatch):
    """One row's reconstruction error is altered where it is produced."""
    score = kops.fused_score

    def off(x, params, tau, use_pallas=False, interpret=True):
        err, flag = score(x, params, tau, use_pallas=use_pallas, interpret=interpret)
        return err.at[0].multiply(1.5), flag

    monkeypatch.setattr(kops, "fused_score", off)


def half_the_rows(monkeypatch):
    """Each micro-batch scores only its first half of rows."""
    score = kops.fused_score

    def half(x, params, tau, use_pallas=False, interpret=True):
        err, flag = score(x, params, tau, use_pallas=use_pallas, interpret=interpret)
        keep = jnp.arange(err.shape[0]) < err.shape[0] // 2
        return jnp.where(keep, err, 0.0), jnp.where(keep, flag, False)

    monkeypatch.setattr(kops, "fused_score", half)


@pytest.mark.parametrize("cell,fault", [
    ("train-paper-n200", state_unchanged),
    ("train-paper-n200", half_the_clients),
    ("train-paper-n200", f1_altered),
    ("serve-paper-bulk", error_altered),
    ("serve-paper-bulk", half_the_rows),
])
def test_a_fault_makes_the_run_incorrect(monkeypatch, cell, fault):
    fault(monkeypatch)
    result = bench_cells.run_tiny(cell)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("cell", ["train-paper-n200", "serve-paper-latency"])
def test_a_sound_tiny_run_is_correct(cell):
    result = bench_cells.run_tiny(cell)
    assert result["correct"] is True, result["checks"]
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == jax.devices()[0].platform
