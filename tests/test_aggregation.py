"""Tests for hierarchical aggregation operators (Eqs. 13, 15, 16)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.core import cooperation as coop
from repro.launch.mesh import make_mesh, shard_map_compat


def test_fog_aggregate_matches_manual():
    updates = jnp.arange(12.0).reshape(6, 2)
    fog_id = jnp.array([0, 0, 1, 1, 1, 2], jnp.int32)
    weights = jnp.array([1.0, 3.0, 2.0, 2.0, 0.0, 5.0])
    out, fog_w = agg.fog_aggregate(updates, fog_id, weights, n_fog=4)
    np.testing.assert_allclose(np.asarray(fog_w), [4.0, 4.0, 5.0, 0.0])
    m0 = (1 * updates[0] + 3 * updates[1]) / 4
    m1 = (2 * updates[2] + 2 * updates[3]) / 4
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(m0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(m1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out[3]), 0.0)  # empty cluster


def test_cooperative_mix_identity_for_noncooperating():
    models = jnp.arange(8.0).reshape(4, 2)
    d = coop.no_cooperation(jnp.zeros((4, 3)))
    mixed = agg.cooperative_mix(models, d)
    np.testing.assert_array_equal(np.asarray(mixed), np.asarray(models))


def test_cooperative_mix_convex_combination():
    models = jnp.array([[0.0], [10.0]])
    d = coop.CoopDecision(
        partner=jnp.array([1, 1], jnp.int32),
        self_weight=jnp.array([0.8, 1.0]),
        partner_weight=jnp.array([0.2, 0.0]),
        cooperates=jnp.array([True, False]),
        dist_m=jnp.zeros((2,)),
    )
    mixed = agg.cooperative_mix(models, d)
    np.testing.assert_allclose(np.asarray(mixed), [[2.0], [10.0]])


def test_global_aggregate_weighted():
    models = jnp.array([[1.0], [2.0], [3.0]])
    w = jnp.array([1.0, 1.0, 2.0])
    out = agg.global_aggregate(models, w)
    np.testing.assert_allclose(np.asarray(out), [(1 + 2 + 6) / 4.0])


def test_hierarchy_equals_flat_when_weights_consistent():
    """Two-level weighted mean == one-level weighted mean (associativity of
    weighted averages) — the algebraic fact the paper's Eq. 13+16 rely on."""
    key = jax.random.key(5)
    updates = jax.random.normal(key, (10, 3))
    weights = jax.random.uniform(jax.random.fold_in(key, 1), (10,)) + 0.1
    fog_id = jnp.array([0, 1, 2, 0, 1, 2, 0, 1, 2, 0], jnp.int32)
    fog_up, fog_w = agg.fog_aggregate(updates, fog_id, weights, 3)
    two_level = agg.global_aggregate(fog_up, fog_w)
    flat = agg.weighted_mean(updates, weights)
    np.testing.assert_allclose(np.asarray(two_level), np.asarray(flat), rtol=1e-5)


def test_hierarchical_mean_shard_map_matches_flat():
    """Mesh two-level reduction == flat weighted mean on a 1x1 mesh."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((1, 1), ("pod", "data"))
    update = jnp.arange(4.0)
    weight = jnp.float32(2.0)

    def f(u, w):
        return agg.hierarchical_mean(u, w, intra_axis="data", inter_axis="pod")

    out = shard_map_compat(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=P()
    )(update, weight)
    np.testing.assert_allclose(np.asarray(out), np.asarray(update))


def test_ring_mix_single_device_identity():
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((1,), ("pod",))
    x = jnp.arange(3.0)
    out = shard_map_compat(
        lambda u: agg.ring_mix(u, 0.3, axis="pod"),
        mesh=mesh, in_specs=(P(),), out_specs=P(),
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


# ---------------------------------------------------------------------------
# Zero-total-weight rounds (dead network) — PR 5 bugfix.
# ---------------------------------------------------------------------------

def test_global_aggregate_zero_weights_holds_prev():
    """A dead-network round must hold the model, not wipe it to zeros."""
    models = jnp.arange(6.0).reshape(3, 2) + 1.0
    prev = jnp.array([7.0, -3.0])
    dead = jnp.zeros((3,))
    held = agg.global_aggregate(models, dead, prev=prev)
    np.testing.assert_array_equal(np.asarray(held), np.asarray(prev))
    # without a carry the legacy zero default is preserved
    np.testing.assert_allclose(np.asarray(agg.global_aggregate(models, dead)), 0.0)
    # live rounds are untouched by the fallback
    live_w = jnp.array([1.0, 0.0, 3.0])
    np.testing.assert_allclose(
        np.asarray(agg.global_aggregate(models, live_w, prev=prev)),
        np.asarray(agg.global_aggregate(models, live_w)),
        rtol=1e-6,
    )


def test_weighted_mean_zero_weights_holds_prev():
    updates = jnp.arange(4.0).reshape(2, 2)
    prev = jnp.array([5.0, 5.0])
    held = agg.weighted_mean(updates, jnp.zeros((2,)), prev=prev)
    np.testing.assert_array_equal(np.asarray(held), np.asarray(prev))


# ---------------------------------------------------------------------------
# Non-finite client updates (graceful degradation guard) — ISSUE 7.
# ---------------------------------------------------------------------------

def test_compress_and_accumulate_zeroes_nonfinite_rows():
    """A client delta carrying Inf/NaN must be zeroed — delta, EF buffer
    AND weight — before it touches the fog sums, independent of the fault
    layer; finite clients are bit-identical with or without the poisoned
    neighbour."""
    from repro.core import compression as comp

    key = jax.random.key(7)
    n, d = 8, 24
    deltas = jax.random.normal(key, (n, d))
    err = jax.random.normal(jax.random.fold_in(key, 1), (n, d)) * 0.1
    fog_id = jnp.arange(n, dtype=jnp.int32) % 2
    weights = jnp.ones((n,))
    cfg = comp.CompressorConfig(rho_s=0.25, quant_bits=8, mode="blockwise")

    poisoned = deltas.at[2, 3].set(jnp.inf).at[5, 0].set(jnp.nan)
    fog_sum, fog_w, new_err = agg.compress_and_accumulate(
        poisoned, err, fog_id, weights, 2, cfg
    )
    assert bool(jnp.all(jnp.isfinite(fog_sum)))
    assert bool(jnp.all(jnp.isfinite(new_err)))
    # The poisoned clients' weight is gone from their fogs.
    np.testing.assert_allclose(np.asarray(fog_w), [3.0, 3.0])

    # Equivalent to excluding them up front (weight 0, zero delta/err).
    excl = jnp.where(jnp.asarray([i in (2, 5) for i in range(n)]))[0]
    w_ref = weights.at[excl].set(0.0)
    d_ref = deltas.at[excl].set(0.0)
    e_ref = err.at[excl].set(0.0)
    ref_sum, ref_w, ref_err = agg.compress_and_accumulate(
        d_ref, e_ref, fog_id, w_ref, 2, cfg
    )
    np.testing.assert_array_equal(np.asarray(fog_sum), np.asarray(ref_sum))
    np.testing.assert_array_equal(np.asarray(fog_w), np.asarray(ref_w))
    np.testing.assert_array_equal(np.asarray(new_err), np.asarray(ref_err))

    # Finite inputs: the guard is an exact no-op.
    g_sum, g_w, g_err = agg.compress_and_accumulate(
        deltas, err, fog_id, weights, 2, cfg
    )
    assert bool(jnp.all(jnp.isfinite(g_sum))) and float(g_w.sum()) == n


def test_battery_exhaustion_holds_model_through_hfl_train():
    """Regression: with every sensor battery-dead, fog weights are all zero
    and hfl.train used to collapse the global model to zeros on round 1;
    now each dead round is an explicit no-op on the params."""
    from repro.core import energy as en
    from repro.core import hfl
    from repro.data.synthetic import SyntheticConfig, generate, normalize
    from repro.launch import experiment as exp
    from repro.models import autoencoder as ae

    ds = normalize(generate(
        jax.random.key(0),
        SyntheticConfig(n_sensors=8, train_len=32, val_len=16, test_len=32),
    ))
    cfg = exp.make_config(
        n_sensors=8, n_fog=2, rounds=3, local_epochs=1,
        energy=en.EnergyParams(e_init_j=0.0, e_min_j=0.0),
    )
    key = jax.random.key(1)
    params0 = ae.init(jax.random.key(2), ds.train.shape[-1], (16, 8, 16))
    # NEAREST would happily pair stale association clusters; the round now
    # feeds battery-aware active cluster sizes into the decision, so a
    # fully dead network also reports zero cooperation links.
    params, metrics = hfl.train(
        key, params0, ae.loss, ds, cfg.replace(rule=hfl.coop.CoopRule.NEAREST)
    )
    assert float(jnp.max(metrics.participation)) == 0.0
    assert float(jnp.max(metrics.coop_links)) == 0.0
    assert float(jnp.max(metrics.e_f2f)) == 0.0
    for p, p0 in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(params0)
    ):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(p0))
    assert not bool(jnp.any(jnp.isnan(metrics.loss)))
