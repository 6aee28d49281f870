"""Tests for serving-under-load upgrades (ISSUE 8 satellites + tentpole).

Deadline-driven partial flush, shape-bucket selection at the boundaries,
wall-clock checkpoint polling on an idle service, int8-quantised serving
weights (parity against f32 — flags identical at the calibrated tau on
the quick-tier dataset, Pallas-interpret vs oracle agreement), the
multi-tenant pin (one compiled program per bucket TOTAL, per-tenant
hot-swap round-tripping from a real ``hfl.train(store=...)`` publish),
and randomized submit/step/tick/drain interleavings where every request
must complete exactly once with its leading shape restored.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointStore
from repro.core import anomaly
from repro.data.synthetic import SyntheticConfig, generate, normalize
from repro.kernels import ops
from repro.loadgen import VirtualClock
from repro.models import autoencoder as ae
from repro.serving import (
    MultiTenantService,
    ScoringService,
    dequantize_params,
    quantize_params,
    score,
    score_q8,
)
from repro.serving.service import ScorePrograms
from tests._hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

D = 12


def _params(seed=0, d=D, hidden=(8, 4, 8)):
    return ae.init(jax.random.key(seed), d, hidden)


def _store(path, params, step=1):
    store = CheckpointStore(str(path))
    store.publish(step, params)
    return store


def _svc(path, clock, params=None, **kw):
    params = _params() if params is None else params
    store = _store(path, params)
    kw.setdefault("tau", 1.0)
    return ScoringService(store, params, clock=clock, **kw)


def _rows(n, seed=0, d=D):
    return np.random.default_rng(seed).standard_normal((n, d)).astype(np.float32)


# ---------------------------------------------------------------------------
# deadline-driven partial flush
# ---------------------------------------------------------------------------

def test_partial_batch_flushes_at_deadline(tmp_path):
    clock = VirtualClock()
    svc = _svc(tmp_path, clock, buckets=(64,), max_wait_s=0.5)
    rid = svc.submit(_rows(10))
    assert svc.pending_rows() == 10
    assert not svc.should_flush()              # neither full nor expired
    assert svc.pump() == 0
    assert svc.next_deadline() == pytest.approx(0.5)
    clock.advance_to(0.49)
    assert svc.tick() == 0                     # still inside the window
    clock.advance_to(0.5)
    assert svc.should_flush()
    assert svc.pump() == 10                    # partial batch went out
    assert svc.stats.partial_flushes == 1
    res = svc.drain()
    assert res[rid].error.shape == (10,)
    # e2e latency = wait-to-deadline + device time: at least the wait.
    assert svc.stats.e2e_latency_s[0] >= 0.5


def test_no_deadline_means_legacy_flush_semantics(tmp_path):
    clock = VirtualClock()
    svc = _svc(tmp_path, clock, buckets=(64,))   # max_wait_s=None
    svc.submit(_rows(10))
    clock.advance(1e6)
    assert svc.next_deadline() is None
    assert not svc.should_flush()
    assert svc.pump() == 0                     # only drain() forces it
    assert len(svc.drain()) == 1


def test_full_bucket_flushes_without_deadline(tmp_path):
    clock = VirtualClock()
    svc = _svc(tmp_path, clock, buckets=(8, 64), max_wait_s=100.0)
    svc.submit(_rows(64))
    assert svc.should_flush()                  # full largest bucket
    assert svc.pump() == 64
    assert svc.stats.partial_flushes == 0


# ---------------------------------------------------------------------------
# shape buckets
# ---------------------------------------------------------------------------

def test_bucket_selection_boundaries(tmp_path):
    clock = VirtualClock()
    svc = _svc(tmp_path, clock, buckets=(128, 1024), max_wait_s=1.0)

    svc.submit(_rows(128))                     # exactly the small bucket
    clock.advance(1.0)
    svc.pump()
    assert svc.stats.compiles_by_bucket == {128: 1}
    assert svc.stats.partial_flushes == 0      # 128 rows fill bucket 128

    svc.submit(_rows(129))                     # one over: big bucket
    clock.advance(1.0)
    svc.pump()
    assert svc.stats.compiles_by_bucket == {128: 1, 1024: 1}
    assert svc.stats.partial_flushes == 1      # 129 rows pad into 1024

    steps = svc.stats.steps
    svc.submit(_rows(1500))                    # over the largest bucket
    clock.advance(1.0)
    svc.pump()
    # 1500 rows = one full 1024 batch + a 476-row partial (the remainder
    # exceeds the 128 bucket, so it pads into 1024) — and REUSING buckets
    # never retraces: the per-bucket compile counts are unchanged.
    assert svc.stats.steps == steps + 2
    assert svc.stats.partial_flushes == 2
    assert svc.stats.compiles_by_bucket == {128: 1, 1024: 1}
    assert svc.pending_rows() == 0
    assert len(svc.drain()) == 3


def test_buckets_sorted_deduped_and_validated(tmp_path):
    clock = VirtualClock()
    svc = _svc(tmp_path, clock, buckets=(256, 64, 256))
    assert svc.buckets == (64, 256)
    assert svc.batch_rows == 256
    with pytest.raises(ValueError):
        _svc(tmp_path / "bad", clock, buckets=(0, 64))


def test_single_bucket_back_compat_batch_rows(tmp_path):
    clock = VirtualClock()
    svc = _svc(tmp_path, clock, batch_rows=96)
    assert svc.buckets == (96,)
    rid = svc.submit(_rows(200))
    res = svc.drain()
    assert res[rid].error.shape == (200,)
    assert svc.stats.compiles_by_bucket == {96: 1}
    assert svc.stats.compiles == 1             # legacy pin still holds


# ---------------------------------------------------------------------------
# wall-clock checkpoint polling (idle hot-swap)
# ---------------------------------------------------------------------------

def test_idle_service_hot_swaps_on_poll_interval(tmp_path):
    params = _params()
    clock = VirtualClock()
    store = _store(tmp_path, params)
    svc = ScoringService(
        store, params, tau=1.0, clock=clock,
        poll_every=10**9, poll_interval_s=5.0,
    )
    store.publish(2, jax.tree_util.tree_map(lambda a: a * 0.5, params))
    clock.advance(4.9)
    svc.tick()
    assert svc.loaded_step == 1                # interval not reached
    clock.advance(0.2)
    svc.tick()                                 # NO scoring steps ran
    assert svc.loaded_step == 2
    assert svc.stats.swaps == 1


def test_submit_also_triggers_interval_poll(tmp_path):
    params = _params()
    clock = VirtualClock()
    store = _store(tmp_path, params)
    svc = ScoringService(
        store, params, tau=1.0, clock=clock,
        poll_every=10**9, poll_interval_s=1.0,
    )
    store.publish(3, params)
    clock.advance(1.5)
    svc.submit(_rows(4))
    assert svc.loaded_step == 3


# ---------------------------------------------------------------------------
# honest stats naming + e2e latency in summary()
# ---------------------------------------------------------------------------

def test_summary_reports_step_and_e2e_latency_separately(tmp_path):
    clock = VirtualClock()
    svc = _svc(tmp_path, clock, buckets=(32,), max_wait_s=2.0)
    svc.submit(_rows(8))
    clock.advance(2.0)
    svc.pump()
    s = svc.stats.summary()
    for key in ("step_p50_ms", "step_p99_ms", "e2e_p50_ms", "e2e_p99_ms",
                "partial_flushes", "compiles_by_bucket"):
        assert key in s, key
    # The old keys misreported device-step time as request latency.
    assert "p50_ms" not in s and "p99_ms" not in s
    # e2e includes the 2s queue wait; the device step does not.
    assert s["e2e_p50_ms"] >= 2000.0
    assert s["step_p50_ms"] < 2000.0


# ---------------------------------------------------------------------------
# int8 serving weights
# ---------------------------------------------------------------------------

def test_int8_off_by_default(tmp_path):
    svc = _svc(tmp_path, VirtualClock())
    assert svc.programs.weight_dtype == "f32"
    assert "qw" not in svc.params[0] and "w" in svc.params[0]


def test_quantize_dequantize_roundtrip_error_bounded():
    params = _params(seed=2, d=32, hidden=(16, 8, 16))
    deq = dequantize_params(quantize_params(params))
    for layer, dlayer in zip(params, deq):
        w = np.asarray(layer["w"])
        err = np.abs(np.asarray(dlayer["w"]) - w)
        # Symmetric per-column int8: error <= half a quantisation step.
        step = np.abs(w).max(axis=0, keepdims=True) / 127.0
        assert np.all(err <= 0.5 * step + 1e-7)
        np.testing.assert_array_equal(dlayer["b"], layer["b"])


def test_score_q8_fused_matches_dequantized_unfused():
    """score_q8 == the core/anomaly oracle on the dequantised weights."""
    params = _params(seed=3, d=32, hidden=(16, 8, 16))
    qp = quantize_params(params)
    x = jnp.asarray(_rows(300, seed=3, d=32))
    fused = score_q8(qp, x, 1.0, use_pallas=False)
    err = anomaly.reconstruction_errors(ae.apply, dequantize_params(qp), x)
    np.testing.assert_allclose(
        np.asarray(fused.error), np.asarray(err), rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_array_equal(
        np.asarray(fused.flag), np.asarray(anomaly.flag_anomalies(err, 1.0))
    )


@pytest.mark.parametrize(
    "r,d,hidden",
    [
        (37, 32, (16, 8, 16)),     # sub-block padding on rows AND features
        (256, 32, (16, 8, 16)),    # exact row tiles
        (130, 130, (64, 8, 64)),   # feature dim > LANES: two-lane padding
    ],
)
def test_fused_score_q8_pallas_interpret_matches_ref(r, d, hidden):
    params = _params(seed=r, d=d, hidden=hidden)
    qp = quantize_params(params)
    x = jax.random.normal(jax.random.key(r), (r, d))
    err_r, flag_r = ops.fused_score_q8(x, qp, 1.0, use_pallas=False)
    err_p, flag_p = ops.fused_score_q8(
        x, qp, 1.0, use_pallas=True, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(err_p), np.asarray(err_r), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(flag_p), np.asarray(flag_r))


def test_score_q8_flags_nonfinite_as_anomalous():
    qp = quantize_params(_params())
    x = np.zeros((4, D), np.float32)
    x[2] = np.nan
    res = score_q8(qp, jnp.asarray(x), jnp.inf, use_pallas=False)
    assert np.asarray(res.flag)[2]
    np.testing.assert_array_equal(np.asarray(res.flag)[[0, 1, 3]], False)


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


def test_int8_flags_identical_to_f32_at_calibrated_tau():
    """The acceptance criterion: on the quick-tier dataset with TRAINED
    params and the Eq. 32 calibrated tau, int8 serving must flag exactly
    the same windows as f32 (the quantisation shift stays inside the
    threshold margin)."""
    params, _, _, ds, _ = _train_tiny()
    d = ds.val.shape[-1]
    val = jnp.asarray(ds.val).reshape(-1, d)
    test = jnp.asarray(ds.test).reshape(-1, d)
    err_val = anomaly.reconstruction_errors(ae.apply, params, val)
    tau = anomaly.calibrate_threshold(err_val, 99.0)
    r32 = score(params, test, tau, use_pallas=False)
    r8 = score_q8(quantize_params(params), test, tau, use_pallas=False)
    np.testing.assert_array_equal(
        np.asarray(r8.flag), np.asarray(r32.flag)
    )
    # Errors shift by at most the int8 tolerance, and both verdict sets
    # are non-trivial (some anomalies flagged, not all).
    rel = np.abs(np.asarray(r8.error) - np.asarray(r32.error))
    rel /= np.abs(np.asarray(r32.error)) + 1e-9
    assert rel.max() < 0.05
    n_flag = int(np.asarray(r32.flag).sum())
    assert 0 < n_flag < test.shape[0]


def test_int8_service_end_to_end_matches_f32_service(tmp_path):
    params, _, p0, ds, _ = _train_tiny(
        store=CheckpointStore(str(tmp_path / "a"))
    )
    clock32, clock8 = VirtualClock(), VirtualClock()
    store_a = CheckpointStore(str(tmp_path / "a"))
    svc32 = ScoringService(store_a, p0, tau=1.0, batch_rows=128, clock=clock32)
    svc8 = ScoringService(
        store_a, p0, tau=1.0, batch_rows=128, clock=clock8,
        weight_dtype="int8",
    )
    telemetry = np.asarray(ds.test[:4])
    rid32 = svc32.submit(telemetry)
    rid8 = svc8.submit(telemetry)
    e32 = svc32.drain()[rid32]
    e8 = svc8.drain()[rid8]
    np.testing.assert_allclose(e8.error, e32.error, rtol=0.05, atol=1e-4)
    assert e8.error.shape == e32.error.shape == (4, 48)


def test_programs_weight_dtype_mismatch_rejected(tmp_path):
    params = _params()
    store = _store(tmp_path, params)
    programs = ScorePrograms(weight_dtype="int8", use_pallas=False)
    with pytest.raises(ValueError, match="int8"):
        ScoringService(store, params, tau=1.0, programs=programs)
    with pytest.raises(ValueError):
        ScorePrograms(weight_dtype="fp4")


# ---------------------------------------------------------------------------
# multi-tenant serving
# ---------------------------------------------------------------------------

def test_multi_tenant_shares_programs_and_isolates_swaps(tmp_path):
    """The acceptance pin: N tenants, real train->publish stores, one
    compiled program per bucket TOTAL; hot-swap stays per-tenant."""
    store_a = CheckpointStore(str(tmp_path / "a"), keep=3)
    params_a, _, p0, ds, _ = _train_tiny(store=store_a)
    store_b = CheckpointStore(str(tmp_path / "b"), keep=3)
    params_b, _, _, _, _ = _train_tiny(store=store_b, rounds=2)

    clock = VirtualClock()
    mt = MultiTenantService(
        p0, buckets=(64, 256), max_wait_s=0.05, clock=clock, use_pallas=False
    )
    svc_a = mt.add_tenant("a", store_a, tau=1.0)
    svc_b = mt.add_tenant("b", store_b, tau=1.0)
    assert svc_a.loaded_step == 3 and svc_b.loaded_step == 2
    assert mt.tenants == ("a", "b")
    with pytest.raises(ValueError):
        mt.add_tenant("a", store_b, tau=1.0)

    # Interleaved submits; batches never mix tenants, so each result must
    # match ITS tenant's params oracle.
    telemetry = np.asarray(ds.test[:4])        # (4, 48, d): 192 rows
    keys = [mt.submit("a", telemetry), mt.submit("b", telemetry),
            mt.submit("a", telemetry[0])]
    clock.advance(1.0)
    mt.pump()
    res = mt.drain()
    assert set(res) == set(keys)

    def oracle(params):
        err = anomaly.reconstruction_errors(
            ae.apply, params, jnp.asarray(telemetry).reshape(-1, ds.val.shape[-1])
        ).reshape(4, 48)
        return np.asarray(err)

    np.testing.assert_allclose(
        res[("a", keys[0][1])].error, oracle(params_a), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        res[("b", keys[1][1])].error, oracle(params_b), rtol=1e-5, atol=1e-5
    )

    # ONE compiled program per bucket, not per tenant.
    used = mt.compiles_by_bucket
    assert used and all(v == 1 for v in used.values()), used
    # Every tenant's stats view IS the shared per-bucket counter.
    assert svc_a.stats.compiles == svc_b.stats.compiles == sum(used.values())

    # Per-tenant hot-swap: publish a new round for tenant b only.
    store_b.publish(9, jax.tree_util.tree_map(lambda a: a * 0.5, params_b))
    swapped = mt.poll()
    assert swapped == {"a": False, "b": True}
    assert svc_b.loaded_step == 9 and svc_a.loaded_step == 3
    # Swap reuses the compiled programs: still one per bucket.
    k = mt.submit("b", telemetry[0])
    clock.advance(1.0)
    mt.pump()
    res2 = mt.drain()
    assert all(v == 1 for v in mt.compiles_by_bucket.values())
    half_err = anomaly.reconstruction_errors(
        ae.apply, jax.tree_util.tree_map(lambda a: 0.5 * a, params_b),
        jnp.asarray(telemetry[0]),
    )
    np.testing.assert_allclose(
        res2[k].error, np.asarray(half_err), rtol=1e-5, atol=1e-5
    )

    summ = mt.summary()
    # The 48-row submit above used the 64 bucket for the first time; the
    # invariant is one trace per bucket EVER, not a frozen bucket set.
    final = mt.compiles_by_bucket
    assert all(v == 1 for v in final.values()), final
    assert summ["compiles"] == sum(final.values())
    assert summ["requests"] == 4
    assert set(summ["tenants"]) == {"a", "b"}


def test_multi_tenant_deadline_fairness(tmp_path):
    """A quiet tenant's expired deadline flushes even while a chatty
    tenant keeps a deeper (but younger) queue."""
    params = _params()
    clock = VirtualClock()
    mt = MultiTenantService(
        params, buckets=(256,), max_wait_s=0.1, clock=clock, use_pallas=False
    )
    mt.add_tenant("quiet", _store(tmp_path / "q", params), tau=1.0)
    mt.add_tenant("chatty", _store(tmp_path / "c", params), tau=1.0)
    # Warm the 256 program first: its COMPILE time would otherwise advance
    # the virtual clock far past every deadline on the first flush.
    mt.submit("quiet", _rows(256))
    assert mt.pump() == 256
    mt.drain()

    mt.submit("quiet", _rows(4))
    clock.advance(0.09)
    t_chatty = clock()
    mt.submit("chatty", _rows(100, seed=1))
    clock.advance(0.02)                        # quiet expired, chatty not
    assert mt.tenant("quiet").should_flush()
    assert not mt.tenant("chatty").should_flush()
    mt.pump()
    assert mt.tenant("quiet").pending_rows() == 0
    assert mt.tenant("chatty").pending_rows() == 100
    assert mt.next_deadline() == pytest.approx(t_chatty + 0.1)
    assert mt.tenant("quiet").stats.e2e_latency_s[-1] >= 0.11 - 1e-9


# ---------------------------------------------------------------------------
# randomized interleavings: every request completes exactly once
# ---------------------------------------------------------------------------

def _run_interleaving(tmp_path, ops_seq, buckets=(16, 64), max_wait_s=0.05):
    clock = VirtualClock()
    svc = _svc(tmp_path, clock, buckets=buckets, max_wait_s=max_wait_s)
    expected: dict[int, tuple] = {}
    results: dict[int, object] = {}
    for op, arg in ops_seq:
        if op == "submit":
            lead, seed = arg
            n = int(np.prod(lead))
            x = _rows(n, seed=seed).reshape(*lead, D)
            expected[svc.submit(x)] = tuple(lead)
        elif op == "advance":
            clock.advance(arg)
        elif op == "step":
            svc.step()
        elif op == "tick":
            svc.tick()
        elif op == "pump":
            svc.pump()
        elif op == "drain":
            results.update(svc.drain())
    results.update(svc.drain())
    return svc, expected, results


def _check_interleaving(svc, expected, results):
    assert set(results) == set(expected), "every request completes exactly once"
    for rid, lead in expected.items():
        assert results[rid].error.shape == lead, (rid, lead)
        assert results[rid].flag.shape == lead
    assert svc.pending_rows() == 0
    assert len(svc.stats.e2e_latency_s) == len(expected)


LEADS = ((3,), (17,), (2, 5), (40,), (1, 1, 4), (70,))


def test_random_interleavings_seeded(tmp_path):
    """Seeded generator variant that always runs (hypothesis is optional
    in this container): random op soups, exact completion accounting."""
    rng = np.random.default_rng(0)
    for case in range(8):
        ops_seq = []
        for i in range(rng.integers(1, 25)):
            k = rng.integers(0, 6)
            if k <= 2:
                ops_seq.append(
                    ("submit", (LEADS[rng.integers(len(LEADS))], int(i)))
                )
            elif k == 3:
                ops_seq.append(("advance", float(rng.uniform(0, 0.1))))
            else:
                ops_seq.append(
                    (("step", "tick", "pump", "drain")[rng.integers(4)], None)
                )
        svc, expected, results = _run_interleaving(
            tmp_path / f"case{case}", ops_seq
        )
        _check_interleaving(svc, expected, results)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="needs hypothesis")
@settings(max_examples=25, deadline=None)
@given(
    st.lists(
        st.one_of(
            st.tuples(
                st.just("submit"),
                st.tuples(st.sampled_from(LEADS), st.integers(0, 99)),
            ),
            st.tuples(st.just("advance"), st.floats(0.0, 0.2)),
            st.tuples(st.sampled_from(("step", "tick", "pump", "drain")),
                      st.none()),
        ),
        max_size=30,
    )
)
def test_random_interleavings_property(tmp_path_factory, ops_seq):
    svc, expected, results = _run_interleaving(
        tmp_path_factory.mktemp("interleave"), ops_seq
    )
    _check_interleaving(svc, expected, results)


# ---------------------------------------------------------------------------
# max_queue admission control (ISSUE 9 satellite): overload sheds load at
# the door instead of growing the deque without bound.
# ---------------------------------------------------------------------------

def test_max_queue_validated(tmp_path):
    with pytest.raises(ValueError, match="max_queue"):
        _svc(tmp_path, VirtualClock(), max_queue=0)


def test_submit_drops_over_cap_and_counts(tmp_path):
    clock = VirtualClock()
    svc = _svc(tmp_path, clock, buckets=(64,), max_queue=2)
    r1 = svc.submit(_rows(4))
    r2 = svc.submit(_rows(4))
    assert r1 is not None and r2 is not None
    # Queue full, nothing flushable (bucket 64, no deadline): reject.
    assert svc.submit(_rows(4)) is None
    assert svc.submit(_rows(4)) is None
    assert svc.stats.dropped == 2
    assert svc.stats.requests == 2             # rejected != admitted
    assert svc.pending_rows() == 8             # queue unchanged by drops
    # Admitted requests still complete exactly once.
    res = svc.drain()
    assert sorted(res) == [r1, r2]
    # ...and a post-flush submit is admitted again.
    assert svc.submit(_rows(4)) is not None
    assert svc.stats.summary()["dropped"] == 2


def test_no_cap_keeps_legacy_unbounded_queue(tmp_path):
    clock = VirtualClock()
    svc = _svc(tmp_path, clock, buckets=(64,))
    rids = [svc.submit(_rows(1)) for _ in range(50)]
    assert all(r is not None for r in rids)
    assert svc.stats.dropped == 0


def test_loadgen_overload_trace_sheds_and_completes(tmp_path):
    """An overload trace against a capped service: drops happen, the
    queue stays bounded, every ADMITTED request completes exactly once,
    and the replay report only counts completions."""
    from repro.loadgen import harness, poisson_trace

    clock = VirtualClock()
    cap = 4
    # Bucket far above what the trace delivers and a deadline beyond its
    # horizon: nothing flushes mid-trace, so the queue fills to the cap
    # and every later arrival is shed at the door.
    svc = _svc(
        tmp_path, clock, buckets=(512,), max_wait_s=100.0, max_queue=cap,
    )
    trace = poisson_trace(
        0, rate_hz=20.0, duration_s=4.0, fleet=8, n_fog=2, rows=4
    )
    report = harness.replay(svc, trace, clock, d=D)
    assert svc.stats.requests == cap
    assert svc.stats.dropped == trace.n_events - cap
    assert report.completed == svc.stats.requests
    assert len(svc.drain()) == 0               # nothing stranded
    assert svc.pending_rows() == 0
