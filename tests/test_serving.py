"""Tests for the online anomaly-scoring subsystem (repro.serving).

Covers equivalence of the fused score path with the ``core/anomaly``
oracle (all-normal / all-anomalous windows and sub-block padding
included), ref-vs-Pallas(interpret) kernel
parity, streaming-vs-one-shot calibration (exact below capacity,
convergent beyond it), the micro-batching service's hot-swap with a PINNED
compile count, ``Engine.score`` trial-vmapped equivalence, and the
train->publish->serve example end to end (subprocess).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointStore
from repro.core import anomaly
from repro.data.synthetic import SyntheticConfig, generate, normalize
from repro.kernels import ops
from repro.models import autoencoder as ae
from repro.serving import ScoringService, StreamingCalibrator
from repro.serving import calibrate as cal
from repro.serving import score as serving_score_fn
from repro.serving.score import score_fleet


def _params(d=32, hidden=(16, 8, 16), seed=1):
    return ae.init(jax.random.key(seed), d, hidden)


def _oracle(params, x, tau):
    err = anomaly.reconstruction_errors(
        ae.apply, params, x.reshape(-1, x.shape[-1])
    ).reshape(x.shape[:-1])
    return err, anomaly.flag_anomalies(err, tau)


@pytest.mark.parametrize(
    "shape",
    [
        (37, 32),          # sub-block row padding (37 < SCORE_ROWS)
        (4, 48, 32),       # (fleet, window, d) telemetry batch
        (300, 32),         # multiple row tiles with a partial tail
    ],
)
def test_fused_score_matches_unfused_anomaly_oracle(shape):
    """serving.score == reconstruction_errors + flag_anomalies to float
    tolerance, flags exactly."""
    params = _params()
    x = jax.random.normal(jax.random.key(3), shape)
    err_o, _ = _oracle(params, x, jnp.inf)
    tau = jnp.percentile(err_o, 60.0)
    flag_o = err_o > tau
    res = serving_score_fn(params, x, tau, use_pallas=False)
    np.testing.assert_allclose(
        np.asarray(res.error), np.asarray(err_o), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(res.flag), np.asarray(flag_o))
    assert res.error.shape == shape[:-1]


@pytest.mark.parametrize("tau,expect", [(np.inf, 0.0), (-1.0, 1.0)])
def test_all_normal_and_all_anomalous_windows(tau, expect):
    """Degenerate thresholds: tau=+inf flags nothing (all-normal), a
    negative tau flags everything (errors are squared norms >= 0)."""
    params = _params()
    x = jax.random.normal(jax.random.key(5), (3, 40, 32))
    for use_pallas in (False, True):
        res = serving_score_fn(
            params, x, tau, use_pallas=use_pallas, interpret=True
        )
        assert float(jnp.mean(res.flag.astype(jnp.float32))) == expect


@pytest.mark.parametrize(
    "r,d,hidden",
    [
        (37, 32, (16, 8, 16)),     # sub-block padding on rows AND features
        (256, 32, (16, 8, 16)),    # exact row tiles
        (130, 130, (64, 8, 64)),   # feature dim > LANES: two-lane padding
    ],
)
def test_fused_score_pallas_interpret_matches_ref(r, d, hidden):
    """The kernel body (interpret mode) must agree with the jnp oracle."""
    params = _params(d, hidden)
    x = jax.random.normal(jax.random.key(r), (r, d))
    err_r, flag_r = ops.fused_score(x, params, 1.0, use_pallas=False)
    err_p, flag_p = ops.fused_score(
        x, params, 1.0, use_pallas=True, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(err_p), np.asarray(err_r), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(flag_p), np.asarray(flag_r))


def test_score_fleet_per_fog_thresholds():
    """Per-fog taus route to each sensor's rows via fog_id."""
    params = _params()
    x = jax.random.normal(jax.random.key(11), (4, 16, 32))
    fog_id = jnp.asarray([0, 1, 0, 1])
    fog_tau = jnp.asarray([jnp.inf, -1.0])   # fog 0 never, fog 1 always
    res = score_fleet(params, x, fog_tau=fog_tau, fog_id=fog_id,
                      use_pallas=False)
    np.testing.assert_array_equal(
        np.asarray(jnp.any(res.flag, axis=1)), [False, True, False, True]
    )
    with pytest.raises(ValueError):
        score_fleet(params, x, tau=1.0, fog_tau=fog_tau, fog_id=fog_id)


def test_streaming_calibration_matches_one_shot_below_capacity():
    """While count <= capacity the reservoir holds every error, so the
    streaming tau equals jnp.percentile (Eq. 32) bit-for-bit."""
    errs = jax.random.uniform(jax.random.key(0), (500,)) * 3.0
    c = StreamingCalibrator(capacity=1024, percentile=99.0)
    for i in range(5):                      # five streaming batches
        c.observe(errs[i * 100 : (i + 1) * 100])
    np.testing.assert_allclose(
        float(c.global_tau), float(jnp.percentile(errs, 99.0)), rtol=1e-6
    )
    assert c.seen == 500


def test_streaming_calibration_per_fog_routing():
    errs = jnp.concatenate([jnp.full((50,), 1.0), jnp.full((50,), 10.0)])
    fog = jnp.concatenate([jnp.zeros((50,), jnp.int32),
                           jnp.ones((50,), jnp.int32)])
    c = StreamingCalibrator(capacity=256, n_fog=3, percentile=50.0)
    c.observe(errs, fog)
    taus = np.asarray(c.taus())
    np.testing.assert_allclose(taus[0], 1.0)
    np.testing.assert_allclose(taus[1], 10.0)
    assert np.isinf(taus[2])                # uncalibrated fog flags nothing
    np.testing.assert_allclose(float(c.global_tau), 5.5)  # median of union


def test_streaming_calibration_converges_beyond_capacity():
    """Past capacity the reservoir is a uniform sample; the streaming tau
    must converge to the one-shot percentile of the WHOLE stream."""
    big = jax.random.uniform(jax.random.key(1), (20000,))
    c = StreamingCalibrator(capacity=2048, percentile=99.0, seed=1)
    for i in range(20):
        c.observe(big[i * 1000 : (i + 1) * 1000])
    assert c.seen == 20000
    t_stream = float(c.global_tau)
    t_oneshot = float(jnp.percentile(big, 99.0))
    assert abs(t_stream - t_oneshot) / t_oneshot < 0.05


def test_reservoir_empty_state_is_inf():
    state = cal.init(jax.random.key(0), capacity=16, n_fog=2)
    assert np.all(np.isinf(np.asarray(cal.threshold(state))))


# ---------------------------------------------------------------------------
# Non-finite telemetry (graceful degradation, ISSUE 7).
# ---------------------------------------------------------------------------

def test_score_flags_nonfinite_errors_as_anomalous():
    """NaN telemetry produces a NaN reconstruction error; ``err > tau`` is
    False for NaN, so without the policy override corrupt rows would pass
    as normal.  The oracle pipeline shows the hazard; the score path must
    flag them True."""
    params = _params()
    x = jax.random.normal(jax.random.key(33), (5, 32))
    x = x.at[1].set(jnp.nan).at[3, 0].set(jnp.inf)
    err_o, flag_o = _oracle(params, x, jnp.inf)
    assert not np.isfinite(np.asarray(err_o)[[1, 3]]).any()
    assert not np.asarray(flag_o).any()
    flag = np.asarray(serving_score_fn(params, x, jnp.inf, use_pallas=False).flag)
    assert flag[1] and flag[3]
    # Finite rows keep the tau=inf verdict: not anomalous.
    np.testing.assert_array_equal(flag[[0, 2, 4]], False)


def test_calibrator_excludes_nonfinite_errors():
    """Algorithm-R insertion skips NaN/Inf errors: they never enter a
    reservoir or advance its count, so thresholds stay finite and match
    the percentile of the finite subset (below capacity)."""
    finite = jax.random.uniform(jax.random.key(2), (80,)) * 3.0
    errs = jnp.concatenate(
        [finite[:40], jnp.asarray([jnp.nan, jnp.inf, -jnp.inf]), finite[40:]]
    )
    c = StreamingCalibrator(capacity=256, percentile=99.0)
    c.observe(errs)
    assert c.seen == 80                      # the 3 corrupt ones never count
    np.testing.assert_allclose(
        float(c.global_tau), float(jnp.percentile(finite, 99.0)), rtol=1e-6
    )
    # Per-fog routing excludes on both the global and the fog row.
    c2 = StreamingCalibrator(capacity=64, n_fog=2, percentile=50.0)
    c2.observe(
        jnp.asarray([1.0, jnp.nan, 3.0]), jnp.asarray([0, 0, 1], jnp.int32)
    )
    taus = np.asarray(c2.taus())
    np.testing.assert_allclose(taus[0], 1.0)
    np.testing.assert_allclose(taus[1], 3.0)
    np.testing.assert_allclose(taus[2], 2.0)
    np.testing.assert_array_equal(np.asarray(c2.state.count), [1, 1, 2])


def _train_tiny(store=None, rounds=3, **kw):
    from repro.core import hfl
    from repro.launch import experiment as exp

    dcfg = SyntheticConfig(n_sensors=8, train_len=48, val_len=24, test_len=48)
    ds = normalize(generate(jax.random.key(0), dcfg))
    p0 = ae.init(jax.random.key(1), ds.train.shape[-1], (16, 8, 16))
    cfg = exp.make_config(n_sensors=8, n_fog=3, rounds=rounds, local_epochs=1)
    params, metrics = hfl.train(
        jax.random.key(2), p0, ae.loss, ds, cfg, store=store, **kw
    )
    return params, metrics, p0, ds, cfg


def test_service_hot_swap_pinned_compile_count(tmp_path):
    """The acceptance pin: mixed-size requests over many micro-batches,
    a mid-stream hot-swap — exactly ONE trace of the score program."""
    store = CheckpointStore(str(tmp_path), keep=3)
    params, _, p0, ds, _ = _train_tiny(store=store)
    svc = ScoringService(store, p0, batch_rows=128, tau=1.0)
    assert svc.loaded_step == 3

    telemetry = np.asarray(ds.test[:4])                 # 192 rows > batch
    r1 = svc.submit(telemetry)
    r2 = svc.submit(np.asarray(ds.test[4, :10]))        # 10 rows
    res = svc.drain()
    assert res[r1].error.shape == (4, 48)
    assert res[r2].flag.shape == (10,)
    err_o, _ = _oracle(params, jnp.asarray(telemetry), 1.0)
    np.testing.assert_allclose(
        res[r1].error, np.asarray(err_o), rtol=1e-5, atol=1e-5
    )

    # Publish new params; the swap is double-buffered (no reload of the
    # active tree) and must not retrace.
    store.publish(9, jax.tree_util.tree_map(lambda a: a * 0.5, params))
    assert svc.poll() is True
    assert svc.loaded_step == 9
    r3 = svc.submit(telemetry)
    res2 = svc.drain()
    err_new, _ = _oracle(
        jax.tree_util.tree_map(lambda a: a * 0.5, params),
        jnp.asarray(telemetry), 1.0,
    )
    np.testing.assert_allclose(
        res2[r3].error, np.asarray(err_new), rtol=1e-5, atol=1e-5
    )
    assert svc.stats.swaps == 1
    assert svc.stats.compiles == 1, svc.stats.summary()
    assert svc.stats.samples == 2 * telemetry.size // 32 + 10
    assert svc.poll() is False                          # nothing newer


def test_service_calibrator_feed(tmp_path):
    """ingest_validation drives the streaming thresholds the service then
    scores against (per-fog routing included) — still one compile."""
    store = CheckpointStore(str(tmp_path), keep=3)
    params, _, p0, ds, cfg = _train_tiny(store=store)
    calib = StreamingCalibrator(capacity=1024, n_fog=3, percentile=99.0)
    svc = ScoringService(store, p0, batch_rows=128, calibrator=calib)
    fog_id = np.arange(8) % 3
    errs = svc.ingest_validation(np.asarray(ds.val), fog_id[:, None])
    # Calibration errors match the oracle on the served params.
    err_o, _ = _oracle(params, jnp.asarray(ds.val), np.inf)
    np.testing.assert_allclose(
        np.asarray(errs), np.asarray(err_o).reshape(-1), rtol=1e-5, atol=1e-5
    )
    # Global tau == one-shot Eq. 32 calibration (below reservoir capacity).
    np.testing.assert_allclose(
        float(calib.global_tau),
        float(anomaly.calibrate_threshold(err_o.reshape(-1), 99.0)),
        rtol=1e-5,
    )
    rid = svc.submit(np.asarray(ds.test[0]), fog=0)
    flag = svc.drain()[rid].flag
    tau0 = float(calib.fog_taus[0])
    err_t, _ = _oracle(params, jnp.asarray(ds.test[0]), tau0)
    np.testing.assert_array_equal(flag, np.asarray(err_t > tau0))
    assert svc.stats.compiles == 1


def test_engine_score_matches_oracle_and_vmaps_trials():
    from repro.engine import Engine

    params = _params()
    x = jax.random.normal(jax.random.key(21), (6, 20, 32))
    err_o, _ = _oracle(params, x, jnp.inf)
    tau = jnp.percentile(err_o, 80.0)
    eng = Engine()
    out = eng.score(params, x, tau)
    np.testing.assert_allclose(
        np.asarray(out.error), np.asarray(err_o), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(out.flag), np.asarray(err_o > tau))
    log = eng.take_log()
    assert log[-1]["kind"] == "score" and log[-1]["fresh_compile"]

    # (S, P) trial grid: distinct params per trial, shared telemetry.
    scales = jnp.asarray([[1.0, 0.5]])
    pstack = jax.tree_util.tree_map(
        lambda a: scales.reshape((1, 2) + (1,) * a.ndim) * a[None, None],
        params,
    )
    xt = jnp.broadcast_to(x, (1, 2) + x.shape)
    out2 = eng.score(pstack, xt, tau, n_trial_axes=2)
    assert out2.error.shape == (1, 2, 6, 20)
    np.testing.assert_allclose(
        np.asarray(out2.error[0, 0]), np.asarray(err_o), rtol=1e-5, atol=1e-5
    )
    half = jax.tree_util.tree_map(lambda a: 0.5 * a, params)
    err_half, _ = _oracle(half, x, tau)
    np.testing.assert_allclose(
        np.asarray(out2.error[0, 1]), np.asarray(err_half), rtol=1e-5,
        atol=1e-5,
    )


def test_engine_run_publishes_to_store(tmp_path):
    """Engine.run(store=...) publishes trial (0,0)'s trained params: the
    restored tree must score identically to the sequential train."""
    from repro.engine import Engine
    from repro.launch import experiment as exp

    dcfg = SyntheticConfig(n_sensors=8, train_len=48, val_len=24, test_len=48)
    ds = normalize(generate(jax.random.key(0), dcfg))
    cfg = exp.make_config(n_sensors=8, n_fog=3, rounds=2, local_epochs=1)
    store = CheckpointStore(str(tmp_path), keep=3)
    eng = Engine()
    run = eng.run("hfl-selective", cfg, (0,), ds, store=store)
    assert "params" not in run.metrics          # popped before EngineRun
    like = ae.init(jax.random.key(9), ds.train.shape[-1], (16, 8, 16))
    restored, step = store.latest(like)
    assert step == cfg.rounds
    # Published params reproduce the cell's own F1 under the paper protocol.
    d = ds.val.shape[-1]
    f1 = anomaly.evaluate_detector(
        ae.apply, restored, ds.val.reshape(-1, d), ds.test.reshape(-1, d),
        ds.test_label.reshape(-1),
    )
    np.testing.assert_allclose(float(f1.f1), float(run.f1[0, 0]), atol=1e-6)


def test_serve_anomaly_example_end_to_end():
    """The acceptance pin, end to end: train -> publish -> serve with a
    mid-stream hot-swap and ZERO recompiles after warmup (compiles == 1).
    Subprocess keeps the example honest as a CLI."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = (
        os.path.abspath(src) + os.pathsep + env.get("PYTHONPATH", "")
    )
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(
        os.path.dirname(__file__), "..", "examples", "serve_anomaly.py"
    )
    proc = subprocess.run(
        [sys.executable, script, "--rounds", "4", "--n-sensors", "8",
         "--train-len", "48", "--batch-rows", "256"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    summary = json.loads(proc.stdout[proc.stdout.index("{"):])
    assert summary["swapped"] is True
    assert summary["service"]["swaps"] >= 1
    assert summary["service"]["compiles"] == 1      # zero recompiles pin
    assert summary["mean_abs_error_shift"] > 0.0    # params really moved
    assert summary["service"]["samples"] > 0
    assert 0.0 <= summary["f1"] <= 1.0
