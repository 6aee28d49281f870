"""repro.telemetry: spans and counters that record only while a profiler
session records, and the spans the scoring service and Engine.run open."""
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import engine as eng_mod
from repro import telemetry
from repro.checkpoint import CheckpointStore
from repro.data.synthetic import SyntheticConfig, generate, normalize
from repro.kernels import fused_agg, ops
from repro.launch import experiment as exp
from repro.loadgen import VirtualClock
from repro.models import autoencoder as ae
from repro.serving.service import ScoringService

D = 12
ROUND_SCOPES = ("round.associate", "round.local_train", "round.aggregate", "round.global",
                "round.energy", "eval.detector")


@pytest.fixture(autouse=True)
def empty_rings():
    telemetry.clear()
    yield
    telemetry.clear()


def _host_span_names(logdir) -> set[str]:
    (path,) = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"), recursive=True)
    return {e.name for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events}


def test_nothing_is_recorded_outside_a_session(monkeypatch):
    made = []
    monkeypatch.setattr(telemetry, "TraceAnnotation", made.append)
    assert not telemetry.recording()
    with telemetry.span("t.outside") as s:
        sum(range(100))
    telemetry.observe("t.values", [1.0, 2.0])
    assert made == []
    assert telemetry.summary() == {}
    assert telemetry.records("t.outside").size == 0
    assert s.seconds > 0.0


def test_a_session_records_spans_and_values_on_the_host_plane(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        assert telemetry.recording()
        for _ in range(3):
            with telemetry.span("t.work"):
                sum(range(1000))
        telemetry.observe("t.values", [0.5, 1.5])
    assert not telemetry.recording()
    work = telemetry.records("t.work")
    assert work.size == 3 and np.all(work > 0)
    np.testing.assert_array_equal(telemetry.records("t.values"), [0.5, 1.5])
    s = telemetry.summary()
    assert s["t.work"]["count"] == 3 and not s["t.work"]["wrapped"]
    assert s["t.values"]["mean"] == 1.0 and s["t.values"]["p50"] == 1.0
    assert "t.work" in _host_span_names(tmp_path)


def test_the_ring_keeps_the_newest_values_and_says_it_wrapped(tmp_path):
    n = telemetry.RING
    with jax.profiler.trace(str(tmp_path)):
        telemetry.observe("t.many", np.arange(10))
        telemetry.observe("t.many", np.arange(10, n + 7))
    kept = telemetry.records("t.many")
    assert kept.size == n
    np.testing.assert_array_equal(np.sort(kept), np.arange(7, n + 7))
    s = telemetry.summary()["t.many"]
    assert s["wrapped"] and s["count"] == n + 7
    telemetry.clear()
    assert telemetry.summary() == {}


def test_scoring_service_spans_and_queue_waits(tmp_path):
    """Per step one assemble / transfer / complete span, per request one
    submit span, and per completed request one queue wait: on the
    service's virtual clock, queue wait + the step's measured transfer
    time is the request's end-to-end latency."""
    params = ae.init(jax.random.key(0), D, (8, 4, 8))
    store = CheckpointStore(str(tmp_path / "ckpt"))
    store.publish(1, params)
    clock = VirtualClock()
    svc = ScoringService(store, params, buckets=(8, 32), tau=1.0, clock=clock)
    rng = np.random.default_rng(0)
    svc.submit(rng.standard_normal((3, D)).astype(np.float32))
    svc.drain()                                   # compiles outside the session
    svc.stats.e2e_latency_s.clear()
    sizes = (5, 9, 20, 2, 7)
    with jax.profiler.trace(str(tmp_path / "trace")):
        for i, n in enumerate(sizes):
            svc.submit(rng.standard_normal((n, D)).astype(np.float32))
            clock.advance(0.001 * (i + 1))
        svc.drain()
    steps = svc.stats.steps - 1
    assert steps == 2                             # 32 rows, then 11
    s = telemetry.summary()
    for name in ("serve.assemble", "serve.transfer", "serve.complete"):
        assert s[name]["count"] == steps, name
    assert s["serve.submit"]["count"] == len(sizes)
    waits = telemetry.records("serve.queue_wait_s")
    assert waits.size == len(sizes) == len(svc.stats.e2e_latency_s)
    # Requests 0-1 finish in step 1, 2-4 in step 2 (request 2's rows
    # straddle the two); the virtual clock moves only by a transfer.
    transfer_s = telemetry.records("serve.transfer") / 1e9
    np.testing.assert_allclose(waits + transfer_s[[0, 0, 1, 1, 1]],
                               np.asarray(svc.stats.e2e_latency_s), rtol=1e-12, atol=1e-15)
    np.testing.assert_allclose(transfer_s, list(svc.stats.step_latency_s)[1:], rtol=1e-12)
    assert {"serve.submit", "serve.assemble", "serve.transfer",
            "serve.complete"} <= _host_span_names(tmp_path / "trace")


@pytest.fixture(scope="module")
def engine_run(tmp_path_factory):
    """Two tiny Engine.run calls with a store under a profiler session,
    after one that compiles: (engine, summary of the spans)."""
    data = normalize(generate(jax.random.key(0), SyntheticConfig(
        n_sensors=12, train_len=48, val_len=24, test_len=48)))
    cfg = exp.make_config(n_sensors=12, n_fog=3, rounds=2, local_epochs=1)

    class Store:
        def publish(self, step, params):
            self.params = params

    eng, store = eng_mod.Engine(), Store()
    eng.run("hfl-selective", cfg, (0,), data, store=store)     # compiles
    telemetry.clear()
    with jax.profiler.trace(str(tmp_path_factory.mktemp("trace"))):
        for seeds in ((1,), (2,)):
            eng.run("hfl-selective", cfg, seeds, data, store=store)
    return eng, telemetry.summary()


def test_engine_run_spans_once_per_call(engine_run):
    _, s = engine_run
    for name in ("engine.prepare", "engine.execute", "engine.publish"):
        assert s[name]["count"] == 2 and s[name]["mean"] > 0, name


def test_round_phases_are_named_scopes_of_the_compiled_program(engine_run):
    """The phases of a round and the evaluation name the ops of the
    compiled program (under vmap a scope reads ``vmap(vmap(<scope>))``)."""
    eng, _ = engine_run
    (prog,) = eng.compiled()
    parts = {p for name in re.findall(r'op_name="([^"]*)"', prog.as_text())
             for p in re.split(r"[/()]", name)}
    for scope in ROUND_SCOPES:
        assert scope in parts, scope


def test_the_oracle_path_records_no_local_train_pack(engine_run):
    _, s = engine_run
    assert "engine.local_train_pack" not in s
    assert "engine.compress_tiles_per_step" not in s


def test_the_pallas_path_records_the_local_train_pack_once_per_job(tmp_path, monkeypatch):
    data = normalize(generate(jax.random.key(0), SyntheticConfig(
        n_sensors=12, train_len=48, val_len=24, test_len=48)))
    cfg = exp.make_config(n_sensors=12, n_fog=3, rounds=1, local_epochs=1)
    # The Pallas local-train path, its kernel body interpreted on this host.
    monkeypatch.setattr(eng_mod.Engine, "resolve_local_solver",
                        lambda self, ls: ls.replace(use_pallas=True, interpret=True))
    eng = eng_mod.Engine()
    with jax.profiler.trace(str(tmp_path)):
        for seeds in ((1,), (2,)):
            eng.run("hfl-selective", cfg, seeds, data)
    dim = data.train.shape[-1]
    pack = ops.local_train_pack((dim, *eng.hidden, dim))
    assert pack == 128 // max(dim, *eng.hidden) > 1
    np.testing.assert_array_equal(telemetry.records("engine.local_train_pack"), [pack, pack])


def test_the_pallas_path_records_the_compress_tiles_once_per_job(tmp_path, monkeypatch):
    """The counter holds the tiles per grid step that the job's compress
    kernel was handed, on the call that traced the program and on a later
    call that reuses it."""
    data = normalize(generate(jax.random.key(0), SyntheticConfig(
        n_sensors=12, train_len=48, val_len=24, test_len=48)))
    cfg = exp.make_config(n_sensors=12, n_fog=3, rounds=1, local_epochs=1)
    cfg = cfg.replace(compressor=cfg.compressor.replace(mode="blockwise"))
    # The Pallas compress path, its kernel body interpreted on this host.
    monkeypatch.setattr(eng_mod.Engine, "resolve_compressor",
                        lambda self, cc: cc.replace(use_pallas=True, interpret=True))
    handed = []
    kernel = ops._compress_aggregate_pallas

    def spy(*args, tiles, **kw):
        handed.append(tiles)
        return kernel(*args, tiles=tiles, **kw)

    monkeypatch.setattr(ops, "_compress_aggregate_pallas", spy)
    eng = eng_mod.Engine()
    with jax.profiler.trace(str(tmp_path)):
        for seeds in ((1,), (2,)):
            eng.run("hfl-selective", cfg, seeds, data)
    tiles = fused_agg.dense_tiles_per_step(12, 3)
    assert tiles > 1 and set(handed) == {tiles}
    np.testing.assert_array_equal(
        telemetry.records("engine.compress_tiles_per_step"), [tiles, tiles])
