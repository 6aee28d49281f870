"""Tests for the fused local-training path (the client phase in one
VMEM-resident operator).

Covers the ISSUE-4 acceptance points: fused-vs-``local_sgd``-scan parity
to float tolerance (plain SGD and FedProx ``mu > 0``, window sizes that do
not divide the batch size, E = 1 and E = 5), Pallas-interpret vs
jnp-oracle parity, the auto-fallback rule for non-AE models, end-to-end
``hfl.train`` / ``flat_fl.train_flat`` equivalence of the fused kernel
with the scan path (the autoencoder as a detector that is not
``fusable``), the engine's local-solver resolution, and the Eq. 21
empty-fog latency fix.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core import cooperation as coop
from repro.core import hfl
from repro.data.pipeline import multi_epoch_batches, multi_epoch_indices
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import autoencoder as ae
from repro.optim.sgd import (
    LocalTrainConfig,
    fusable_params,
    local_sgd,
    make_client_solver,
)

D = 32
HIDDEN = (16, 8, 16)


def _params(seed=1, dim=D, hidden=HIDDEN):
    return ae.init(jax.random.key(seed), dim, hidden)


def _clients(n, window, seed=0):
    return jax.random.normal(jax.random.key(seed), (n, window, D))


def _scan_detector():
    """The paper autoencoder on the scan path: per-client ``local_sgd``
    over a gathered (E * nb, bs, D) batch stream."""
    return dataclasses.replace(ae.detector(), fusable=False)


def _legacy(params, data, keys, batch_size, epochs, lr, mu):
    solver = make_client_solver(
        _scan_detector(), batch_size=batch_size, epochs=epochs, lr=lr,
        prox_mu=mu,
    )
    deltas, stats = solver(params, data, keys)
    return deltas, stats["loss"]


@pytest.mark.parametrize(
    "window,batch_size,epochs",
    [
        (64, 32, 1),      # E = 1
        (64, 32, 5),      # E = 5
        (70, 32, 3),      # window does not divide the batch size
        (40, 16, 2),      # small batches, partial window use
    ],
)
@pytest.mark.parametrize("mu", [0.0, 0.01])
def test_fused_ref_matches_scan(window, batch_size, epochs, mu):
    """ops.local_train (jnp oracle path) == vmapped local_sgd /
    proximal_local_sgd over multi_epoch_batches, batch for batch."""
    params = _params()
    data = _clients(4, window)
    keys = jax.random.split(jax.random.key(3), 4)
    d_leg, l_leg = _legacy(params, data, keys, batch_size, epochs, 0.05, mu)
    idx = jax.vmap(
        lambda k: multi_epoch_indices(k, window, batch_size, epochs)
    )(keys)
    d_ref, l_ref = ops.local_train(
        params, data, idx, 0.05, mu, use_pallas=False
    )
    np.testing.assert_allclose(
        np.asarray(d_ref), np.asarray(d_leg), rtol=1e-5, atol=1e-7
    )
    np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l_leg), rtol=1e-6)


def _idx(n, window, batch_size, epochs, seed=4):
    keys = jax.random.split(jax.random.key(seed), n)
    return jax.vmap(
        lambda k: multi_epoch_indices(k, window, batch_size, epochs)
    )(keys)


@pytest.mark.parametrize(
    "window,batch_size,epochs,mu,dim,n",
    [
        pytest.param(64, 32, 1, 0.0, D, 3, id="64-32-1-0.0"),
        pytest.param(64, 32, 5, 0.0, D, 3, id="64-32-5-0.0"),
        pytest.param(70, 32, 3, 0.01, D, 3, id="70-32-3-0.01"),
        pytest.param(40, 16, 2, 0.0, D, 3, id="40-16-2-0.0"),
    ] + [
        # D = 32 / 38 / 55 / 100 packs P = 4 / 3 / 2 / 1 clients a tile;
        # N = 1 and 7 leave pad clients in the last pack.
        pytest.param(64, 32, 1, mu, dim, n, id=f"D{dim}-N{n}-mu{mu}")
        for dim in (32, 38, 55, 100) for n in (1, 7, 8) for mu in (0.0, 0.01)
    ],
)
def test_pallas_interpret_matches_oracle(window, batch_size, epochs, mu, dim, n):
    """The kernel body (interpret mode) must agree with the jnp oracle:
    identical batch assembly from the resident window, manual backward ==
    autodiff to float tolerance, whatever the packing."""
    params = _params(dim=dim)
    data = jax.random.normal(jax.random.key(window), (n, window, dim))
    idx = _idx(n, window, batch_size, epochs)
    d_ref, l_ref = ops.local_train(
        params, data, idx, 0.05, mu, use_pallas=False
    )
    d_pl, l_pl = ops.local_train(
        params, data, idx, 0.05, mu, use_pallas=True, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(d_pl), np.asarray(d_ref), rtol=1e-4, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(l_pl), np.asarray(l_ref), rtol=1e-5, atol=1e-7
    )


def test_packed_clients_equal_one_client_per_tile():
    """Off-block weights never leak: 7 paper clients packed 4 to a tile
    (one pad client in the second pack), FedProx on, give exactly the
    deltas of the one-client-per-tile layout.  That layout is reached by
    zero-padding the detector's input to 65 features (widths over 64 pack
    one client a tile); the padding adds exact zeros only, so the kernel
    tiles are those of the 32-feature detector at P = 1."""
    wide = 65
    n, window = 7, 64
    params = _params()
    data = _clients(n, window)
    idx = _idx(n, window, 32, 2)
    assert ops.local_train_pack((D, *HIDDEN, D)) == 4
    assert ops.local_train_pack((wide, *HIDDEN, wide)) == 1
    d_packed, l_packed = ops.local_train(
        params, data, idx, 0.05, 0.01, use_pallas=True, interpret=True
    )

    params_wide = [dict(layer) for layer in params]
    params_wide[0]["w"] = jnp.pad(params[0]["w"], ((0, wide - D), (0, 0)))
    params_wide[-1]["w"] = jnp.pad(params[-1]["w"], ((0, 0), (0, wide - D)))
    params_wide[-1]["b"] = jnp.pad(params[-1]["b"], (0, wide - D))
    data_wide = jnp.pad(data, ((0, 0), (0, 0), (0, wide - D)))
    d_one, l_one = ops.local_train(
        params_wide, data_wide, idx, 0.05, 0.01, use_pallas=True,
        interpret=True,
    )
    unravel = ravel_pytree(params_wide)[1]
    got = [unravel(row) for row in d_one]
    for i in range(n):
        # the padded features' deltas are exact zeros
        assert not np.any(np.asarray(got[i][0]["w"][D:]))
        assert not np.any(np.asarray(got[i][-1]["w"][:, D:]))
        assert not np.any(np.asarray(got[i][-1]["b"][D:]))
        got[i][0]["w"] = got[i][0]["w"][:D]
        got[i][-1]["w"] = got[i][-1]["w"][:, :D]
        got[i][-1]["b"] = got[i][-1]["b"][:D]
    d_one_narrow = jnp.stack([ravel_pytree(g)[0] for g in got])
    np.testing.assert_array_equal(np.asarray(d_packed), np.asarray(d_one_narrow))
    np.testing.assert_allclose(
        np.asarray(l_packed), np.asarray(l_one), rtol=1e-6
    )


def test_solver_dispatches_fused_and_matches_scan():
    """make_client_solver with the default config routes the paper AE
    through the fused operator and reproduces the scan path."""
    params = _params()
    data = _clients(5, 64)
    keys = jax.random.split(jax.random.key(5), 5)
    fused = make_client_solver(
        ae.loss, batch_size=32, epochs=2, lr=0.05
    )
    d_f, l_f = fused(params, data, keys)
    d_s, l_s = _legacy(params, data, keys, 32, 2, 0.05, 0.0)
    assert d_f.shape == (5, ravel_pytree(params)[0].shape[0])
    np.testing.assert_allclose(
        np.asarray(d_f), np.asarray(d_s), rtol=1e-5, atol=1e-7
    )
    np.testing.assert_allclose(np.asarray(l_f), np.asarray(l_s), rtol=1e-6)


def test_non_ae_models_fall_back():
    """Anything the kernel cannot express must silently take the scan
    path: non-AE param structures and non-AE losses."""
    assert fusable_params(_params())
    # dict-of-arrays params (LLM-style) are not fusable
    assert not fusable_params({"w": jnp.zeros((4, 4))})
    # broken layer chaining is not fusable
    bad = [{"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))},
           {"w": jnp.zeros((5, 8)), "b": jnp.zeros((8,))}]
    assert not fusable_params(bad)
    # encoder-only stacks (out dim != in dim) are not a reconstruction
    enc = [{"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}]
    assert not fusable_params(enc)

    # a custom loss over AE-shaped params must NOT hit the AE kernel:
    # the solver with a quadratic loss equals the legacy scan of that loss
    def quad_loss(params, batch):
        flat, _ = ravel_pytree(params)
        return jnp.sum(flat**2) + 0.0 * jnp.sum(batch)

    params = _params()
    data = _clients(3, 64)
    keys = jax.random.split(jax.random.key(6), 3)
    solver = make_client_solver(
        quad_loss, batch_size=32, epochs=1, lr=0.05
    )
    d_c, _ = solver(params, data, keys)

    def scan_one(dd, kk):
        batches = multi_epoch_batches(kk, dd, 32, 1)
        p1, _ = local_sgd(quad_loss, params, batches, 0.05)
        return ravel_pytree(jax.tree_util.tree_map(jnp.subtract, p1, params))[0]

    d_l = jax.vmap(scan_one)(data, keys)
    np.testing.assert_array_equal(np.asarray(d_c), np.asarray(d_l))


def _tiny_setup(prox_mu=0.0):
    from repro.data.synthetic import SyntheticConfig, generate, normalize
    from repro.launch import experiment as exp

    dcfg = SyntheticConfig(n_sensors=10, train_len=48, val_len=24, test_len=48)
    ds = normalize(generate(jax.random.key(0), dcfg))
    params0 = ae.init(jax.random.key(1), ds.train.shape[-1], HIDDEN)
    cfg = exp.make_config(
        n_sensors=10, n_fog=3, rounds=2, local_epochs=2, prox_mu=prox_mu,
    )
    return ds, params0, cfg


@pytest.mark.parametrize("prox_mu", [0.0, 0.01])
def test_hfl_train_fused_matches_unfused(prox_mu):
    """End to end: hfl.train with the fused kernel == the scan path (the
    autoencoder as a detector that is not fusable) to float tolerance."""
    ds, params0, cfg = _tiny_setup(prox_mu)
    p1, m1 = hfl.train(jax.random.key(2), params0, ae.loss, ds, cfg)
    p2, m2 = hfl.train(jax.random.key(2), params0, _scan_detector(), ds, cfg)
    np.testing.assert_allclose(
        np.asarray(m1.loss), np.asarray(m2.loss), rtol=1e-5
    )
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_flat_train_fused_matches_unfused():
    from repro.core import flat_fl

    ds, params0, cfg = _tiny_setup(prox_mu=0.01)   # FedProx in-kernel
    p1, m1 = flat_fl.train_flat(jax.random.key(2), params0, ae.loss, ds, cfg)
    p2, m2 = flat_fl.train_flat(
        jax.random.key(2), params0, _scan_detector(), ds, cfg
    )
    np.testing.assert_allclose(
        np.asarray(m1.loss), np.asarray(m2.loss), rtol=1e-5
    )
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_engine_resolves_local_solver():
    from repro import engine as eng_mod

    eng = eng_mod.Engine()
    ls = eng.resolve_local_solver(LocalTrainConfig())
    assert ls.use_pallas == eng_mod.default_use_pallas()
    assert ls.interpret == (not ls.use_pallas)
    assert eng.resolve_local_solver(ls) == ls
    assert eng.resolve_config(hfl.HFLConfig()).local_solver == ls
    # the detector, not the solver config, picks the scan path: an engine
    # training a detector that is not fusable resolves the same backend
    scan = eng_mod.Engine(detector=_scan_detector())
    assert scan.resolve_config(hfl.HFLConfig()).local_solver == ls


def test_mesh_pod_local_epochs_runs_and_degenerates():
    """core/mesh_fl routes through optim/sgd: E=1 keeps the historical
    gradient-exchange numerics; E>1 (delta exchange) still learns."""
    from repro import configs
    from repro.core import mesh_fl
    from repro.models import api

    cfg = configs.get("llama3_8b", reduced=True).replace(learning_rate=1e-2)
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    key = jax.random.key(0)
    params = api.init_params(key, cfg)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab_size)}

    step1 = mesh_fl.make_pod_hfl_train_step(cfg, mesh, local_epochs=1)
    step2 = mesh_fl.make_pod_hfl_train_step(cfg, mesh, local_epochs=2)
    # production-scale lr: the f32-upcast local steps must still produce
    # nonzero exchanged deltas (raw-bf16 steps would round |lr*g| << |p|
    # to zero and leave the EF residual exactly zero)
    step_small = mesh_fl.make_pod_hfl_train_step(
        cfg.replace(learning_rate=1e-4), mesh, local_epochs=2
    )
    with mesh:
        err = mesh_fl.init_err(params, n_pods=1)
        p1, _, l1 = jax.jit(step1)(params, err, batch)
        p2, _, l2 = jax.jit(step2)(params, err, batch)
        _, err_small, _ = jax.jit(step_small)(params, err, batch)
    moved = sum(float(jnp.sum(jnp.abs(e)))
                for e in jax.tree_util.tree_leaves(err_small))
    assert moved > 0.0
    # E=2 reports the mean over both local passes; the second pass re-visits
    # the same batch after a step, so the mean must not exceed the E=1 loss.
    assert float(l2) <= float(l1) + 1e-6
    # E=2 moves further than E=1 from the same start
    d1 = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
             for a, b in zip(jax.tree_util.tree_leaves(p1),
                             jax.tree_util.tree_leaves(params)))
    d2 = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
             for a, b in zip(jax.tree_util.tree_leaves(p2),
                             jax.tree_util.tree_leaves(params)))
    assert d2 > d1 > 0.0


def test_empty_fog_phantom_exchange_does_not_set_latency():
    """Eq. 21 regression pin: an empty fog paired with a distant partner
    (cooperates=True but fog_active=False) must not contribute a
    fog-to-fog latency term — same mask as the Eq. 18 energy."""
    cfg = hfl.HFLConfig()
    l_u, l_full = 1000.0, 43264.0
    active = jnp.array([True, True])
    sensor_dist = jnp.array([200.0, 300.0])
    fog_active = jnp.array([True, False])       # fog 1 is EMPTY
    fg_dist = jnp.array([400.0, 500.0])
    # both fogs nominally cooperate; the empty one with a huge link
    def _decision(coop_mask):
        return coop.CoopDecision(
            partner=jnp.array([1, 0], jnp.int32),
            self_weight=jnp.array([0.8, 0.8]),
            partner_weight=jnp.array([0.2, 0.2]),
            cooperates=jnp.array(coop_mask),
            dist_m=jnp.array([350.0, 4000.0]),
        )

    decision = _decision([True, True])
    lat = hfl.comm_latency_s(
        l_u, l_full, active, sensor_dist, decision, fog_active, fg_dist,
        cfg.channel,
    )
    # dropping the phantom pair entirely must give the same latency
    no_phantom = _decision([True, False])
    lat_ref = hfl.comm_latency_s(
        l_u, l_full, active, sensor_dist, no_phantom, fog_active, fg_dist,
        cfg.channel,
    )
    np.testing.assert_allclose(float(lat), float(lat_ref))
    # sanity: with members in fog 1 the 4 km exchange WOULD dominate
    lat_full = hfl.comm_latency_s(
        l_u, l_full, active, sensor_dist, decision,
        jnp.array([True, True]), fg_dist, cfg.channel,
    )
    assert float(lat_full) > float(lat)


def test_publish_path_donation_keeps_scan_numerics():
    """The publish-path step_fn donates its carry; numerics must stay
    identical to the scan path and the caller's init params must remain
    usable afterwards."""
    from repro.checkpoint import CheckpointStore
    import tempfile

    ds, params0, cfg = _tiny_setup()
    with tempfile.TemporaryDirectory() as tmp:
        store = CheckpointStore(tmp, keep=5)
        p_pub, m_pub = hfl.train(
            jax.random.key(2), params0, ae.loss, ds, cfg, store=store
        )
        # init params were NOT donated away
        _ = jax.block_until_ready(ravel_pytree(params0)[0] + 0.0)
        p_scan, m_scan = hfl.train(jax.random.key(2), params0, ae.loss, ds, cfg)
        np.testing.assert_allclose(
            np.asarray(m_pub.loss), np.asarray(m_scan.loss), rtol=1e-6
        )
        for a, b in zip(jax.tree_util.tree_leaves(p_pub),
                        jax.tree_util.tree_leaves(p_scan)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
