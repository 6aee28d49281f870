"""Tests for the fused compress-and-aggregate path.

Covers the ISSUE-2 acceptance points: ref-oracle parity of the fused op
against the unfused compress -> fog_aggregate pipeline (random cluster
assignments, zero-weight non-participants, the n < BLOCK_ELEMS padding
edge), Pallas-interpret vs jnp-oracle parity, the round-loop dispatch
(fused vs ``CompressorConfig(fused=False)``), and shard_map-vs-single-
device equivalence on a forced multi-device CPU mesh (subprocess, since
XLA device flags must be set before jax initialises).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation as agg
from repro.core import compression as comp
from repro.kernels import fused_agg, ops

N_FOG = 4


def _inputs(n_clients, d, seed=0, zero_weight_every=3):
    key = jax.random.key(seed)
    deltas = jax.random.normal(key, (n_clients, d))
    err = jax.random.normal(jax.random.fold_in(key, 1), (n_clients, d)) * 0.1
    fog_id = jax.random.randint(
        jax.random.fold_in(key, 2), (n_clients,), 0, N_FOG
    ).astype(jnp.int32)
    weights = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (n_clients,)))
    # zero-weight non-participants must not contribute to the fog sums
    weights = jnp.where(jnp.arange(n_clients) % zero_weight_every == 0, 0.0, weights)
    return deltas, err, fog_id, weights


def _unfused(deltas, err, fog_id, weights, cfg):
    recon, new_err = jax.vmap(
        lambda d_, e_: comp.compress_update(d_, e_, cfg)
    )(deltas, err)
    fog_up, fog_w = agg.fog_aggregate(recon, fog_id, weights, N_FOG)
    return fog_up, fog_w, new_err


@pytest.mark.parametrize(
    "d",
    [
        1352,        # n < BLOCK_ELEMS: single padded tile (paper autoencoder)
        8192,        # exactly one tile
        20000,       # three tiles with a partial tail
    ],
)
def test_fused_blockwise_matches_unfused_pipeline(d):
    """compress_and_aggregate == per-client compress_update + fog_aggregate
    to float tolerance on random cluster assignments."""
    deltas, err, fog_id, weights = _inputs(11, d)
    cfg = comp.CompressorConfig(rho_s=0.05, quant_bits=8, mode="blockwise")
    fog_up, fog_w, new_err = agg.compress_and_aggregate(
        deltas, err, fog_id, weights, N_FOG, cfg
    )
    ref_up, ref_w, ref_err = _unfused(
        deltas, err, fog_id, weights, cfg.replace(fused=False)
    )
    np.testing.assert_allclose(np.asarray(fog_w), np.asarray(ref_w), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(fog_up), np.asarray(ref_up), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(new_err), np.asarray(ref_err), atol=1e-6
    )


def test_fused_global_matches_unfused_pipeline():
    """mode='global' routes through the same entry point with identical
    numerics (exact global Top-K + global-scale quantisation)."""
    deltas, err, fog_id, weights = _inputs(9, 1352, seed=4)
    cfg = comp.CompressorConfig(rho_s=0.05, quant_bits=8, mode="global")
    fog_up, fog_w, new_err = agg.compress_and_aggregate(
        deltas, err, fog_id, weights, N_FOG, cfg
    )
    ref_up, ref_w, ref_err = _unfused(deltas, err, fog_id, weights, cfg)
    np.testing.assert_allclose(
        np.asarray(fog_up), np.asarray(ref_up), rtol=1e-5, atol=1e-7
    )
    np.testing.assert_allclose(np.asarray(new_err), np.asarray(ref_err), atol=1e-7)


def test_fused_topk_only_matches_unfused_pipeline():
    """quant_bits=32 (sparsify-only) dispatches without the int8 round-trip."""
    deltas, err, fog_id, weights = _inputs(7, 9000, seed=5)
    cfg = comp.CompressorConfig(rho_s=0.2, quant_bits=32, mode="blockwise")
    fog_up, _, new_err = agg.compress_and_aggregate(
        deltas, err, fog_id, weights, N_FOG, cfg
    )
    ref_up, _, ref_err = _unfused(
        deltas, err, fog_id, weights, cfg.replace(fused=False)
    )
    np.testing.assert_allclose(
        np.asarray(fog_up), np.asarray(ref_up), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(np.asarray(new_err), np.asarray(ref_err), atol=1e-6)


def test_zero_weight_clients_do_not_contribute():
    """Non-participants (weight 0) leave the fog sums unchanged but still
    get their error buffers advanced (the round loop masks those)."""
    deltas, err, fog_id, weights = _inputs(8, 1352, zero_weight_every=2)
    cfg = comp.CompressorConfig(rho_s=0.05, quant_bits=8, mode="blockwise")
    fog_up, fog_w, new_err = agg.compress_and_aggregate(
        deltas, err, fog_id, weights, N_FOG, cfg
    )
    keep = np.asarray(weights) > 0
    # removing zero-weight clients entirely gives the same aggregates
    fog_up2, fog_w2, _ = agg.compress_and_aggregate(
        deltas[keep], err[keep], fog_id[keep], weights[keep], N_FOG, cfg
    )
    np.testing.assert_allclose(np.asarray(fog_w), np.asarray(fog_w2), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(fog_up), np.asarray(fog_up2), rtol=1e-5, atol=1e-6
    )
    # but the EF buffers of zero-weight clients still advanced
    assert not np.allclose(np.asarray(new_err[~keep]), np.asarray(err[~keep]))


def test_empty_fog_gets_zero_update():
    deltas, err, _, weights = _inputs(6, 1352)
    fog_id = jnp.zeros((6,), jnp.int32)  # everyone in cluster 0
    cfg = comp.CompressorConfig(rho_s=0.05, quant_bits=8, mode="blockwise")
    fog_up, fog_w, _ = agg.compress_and_aggregate(
        deltas, err, fog_id, jnp.abs(weights) + 0.1, N_FOG, cfg
    )
    np.testing.assert_array_equal(np.asarray(fog_w[1:]), 0.0)
    np.testing.assert_array_equal(np.asarray(fog_up[1:]), 0.0)


@pytest.mark.parametrize("d", [1352, 8192 + 17, 65536])
@pytest.mark.parametrize("quantize", [True, False])
def test_pallas_interpret_matches_ref(d, quantize):
    """The fused kernel body (interpret mode) must agree with the jnp
    oracle — same bisection threshold and int8 rules."""
    deltas, err, fog_id, weights = _inputs(6, d, seed=d)
    fs_r, ne_r = ops.compress_aggregate(
        deltas, err, fog_id, weights, N_FOG, 0.05, quantize=quantize,
        use_pallas=False,
    )
    fs_p, ne_p = ops.compress_aggregate(
        deltas, err, fog_id, weights, N_FOG, 0.05, quantize=quantize,
        use_pallas=True, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(fs_p), np.asarray(fs_r), rtol=1e-5, atol=1e-4
    )
    np.testing.assert_allclose(np.asarray(ne_p), np.asarray(ne_r), atol=1e-5)


@pytest.mark.parametrize(
    "d,k_frac,quantize",
    [(1352, 68 / 8192, True), (20000, 0.05, False), (8192, 0.3, True)],
)
def test_wire_pallas_interpret_matches_ref(d, k_frac, quantize):
    """The wire emitter's in-kernel compaction packs exactly the oracle's
    slots (survivors in ascending coordinate order, padding slots index 0 /
    code 0), and the wire scatter-accumulate reproduces the oracle's fog
    sums.  Client 0 is all-zero: nothing survives its bisection."""
    deltas, err, fog_id, weights = _inputs(5, d, seed=d)
    deltas, err = deltas.at[0].set(0.0), err.at[0].set(0.0)
    ref = ops.compress_wire(deltas, err, k_frac, quantize, use_pallas=False)
    pal = ops.compress_wire(
        deltas, err, k_frac, quantize, use_pallas=True, interpret=True
    )
    idx, q, scale, new_err = (np.asarray(a) for a in pal)
    np.testing.assert_array_equal(idx, np.asarray(ref[0]))
    np.testing.assert_array_equal(q, np.asarray(ref[1]))
    np.testing.assert_array_equal(scale, np.asarray(ref[2]))
    np.testing.assert_allclose(new_err, np.asarray(ref[3]), rtol=0, atol=1e-6)
    assert not q[0].any() and not idx[0].any()
    kept = q != 0
    for row_idx, row_kept in zip(idx.reshape(-1, idx.shape[-1]),
                                 kept.reshape(-1, kept.shape[-1])):
        assert np.all(np.diff(row_idx[row_kept]) > 0)
    fog_r = ops.wire_aggregate(*ref[:3], fog_id, weights, N_FOG, d)
    fog_p = ops.wire_aggregate(
        *pal[:3], fog_id, weights, N_FOG, d, use_pallas=True, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(fog_p), np.asarray(fog_r))


@pytest.mark.parametrize(
    "n,d,n_fog,tiles,quantize",
    [
        (13, 3 * 8192 + 17, N_FOG, 8, True),      # ragged last step, both kernels
        (13, 3 * 8192 + 17, N_FOG, None, True),   # the tiles chosen from shapes
        (10, 1352, 10, None, True),               # robust path: identity segments
        (10, 1352, 10, 4, True),                  # identity, ragged
        (7, 9000, N_FOG, 3, False),               # sparsify-only, ragged
    ],
    ids=["ragged", "chosen", "identity", "identity-ragged", "topk-only"],
)
def test_tiles_per_step_are_bit_identical_to_one_tile_a_step(
    n, d, n_fog, tiles, quantize
):
    """Bisecting T tiles together in one grid step gives every tile the
    threshold, codes, EF row and fog contribution it gets alone, and the
    fog sums keep their client order.  Client 1 is all-zero."""
    deltas, err, fog_id, weights = _inputs(n, d, seed=n + d)
    if n_fog == n:
        fog_id = jnp.arange(n, dtype=jnp.int32)
    deltas, err = deltas.at[1].set(0.0), err.at[1].set(0.0)
    blocks, _ = ops._pad_blocks_batch(deltas)
    err_blocks, _ = ops._pad_blocks_batch(err)
    k = ops.wire_k(0.05)
    if tiles is None:
        assert fused_agg.dense_tiles_per_step(n, n_fog) > 1
        assert fused_agg.wire_tiles_per_step(
            n, blocks.shape[1], fused_agg.slot_pad(k)) > 1

    def dense(t):
        return fused_agg.compress_aggregate_blocks(
            blocks, err_blocks, fog_id, weights, n_fog, k, quantize,
            tiles=t)

    def wire(t):
        return fused_agg.compress_wire_blocks(
            blocks, err_blocks, k, quantize, tiles=t)

    for kernel in (dense, wire):
        for got, want in zip(kernel(tiles), kernel(1)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_round_loop_fused_matches_unfused():
    """End-to-end: hfl.train with the fused default == the legacy
    per-client pipeline (CompressorConfig(fused=False))."""
    from repro.data.synthetic import SyntheticConfig, generate, normalize
    from repro.launch import experiment as exp
    from repro.models import autoencoder as ae
    from repro.core import hfl

    dcfg = SyntheticConfig(n_sensors=10, train_len=48, val_len=24, test_len=48)
    ds = normalize(generate(jax.random.key(0), dcfg))
    params0 = ae.init(jax.random.key(1), ds.train.shape[-1], (16, 8, 16))
    cc = comp.CompressorConfig(rho_s=0.05, quant_bits=8, mode="blockwise")
    cfg = exp.make_config(n_sensors=10, n_fog=3, rounds=2, local_epochs=1,
                          compressor=cc)
    p1, m1 = hfl.train(jax.random.key(2), params0, ae.loss, ds, cfg)
    p2, m2 = hfl.train(
        jax.random.key(2), params0, ae.loss, ds,
        cfg.replace(compressor=cc.replace(fused=False)),
    )
    np.testing.assert_allclose(
        np.asarray(m1.loss), np.asarray(m2.loss), rtol=1e-5
    )
    for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


_SHMAP_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import hfl, flat_fl
    from repro.data.synthetic import SyntheticConfig, generate, normalize
    from repro.launch import experiment as exp
    from repro.launch import sharding
    from repro.models import autoencoder as ae
    from repro import engine as eng_mod

    assert len(jax.devices()) == 4, jax.devices()
    mesh = sharding.client_mesh()
    assert mesh.axis_names == ("data",) and mesh.size == 4

    cfg = exp.make_config(n_sensors=8, n_fog=3, rounds=2, local_epochs=1)
    dcfg = SyntheticConfig(n_sensors=8, train_len=48, val_len=24, test_len=48)
    ds = normalize(generate(jax.random.key(0), dcfg))
    params0 = ae.init(jax.random.key(1), ds.train.shape[-1], (16, 8, 16))

    for fn in (hfl.train, flat_fl.train_flat):
        p1, m1 = jax.jit(lambda: fn(jax.random.key(2), params0, ae.loss, ds, cfg))()
        p2, m2 = jax.jit(
            lambda: fn(jax.random.key(2), params0, ae.loss, ds, cfg,
                       client_mesh=mesh)
        )()
        np.testing.assert_allclose(
            np.asarray(m1.loss), np.asarray(m2.loss), rtol=1e-4
        )
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    # engine opt-in: shard_clients cells must match default placement
    def make_ds(seed):
        return normalize(generate(jax.random.key(seed), dcfg))

    r1 = eng_mod.Engine().run("hfl-selective", cfg, (0, 1), make_ds)
    sh_eng = eng_mod.Engine(shard_clients=True)
    r2 = sh_eng.run("hfl-selective", cfg, (0, 1), make_ds)
    assert sh_eng.take_log()[0]["client_sharded"] is True
    np.testing.assert_allclose(
        np.asarray(r1.losses), np.asarray(r2.losses), rtol=1e-4
    )
    np.testing.assert_allclose(np.asarray(r1.f1), np.asarray(r2.f1), atol=1e-6)

    # a fleet the device count does not divide is refused, not run on one
    odd = exp.make_config(n_sensors=6, n_fog=3, rounds=1, local_epochs=1)
    odd_ds = normalize(generate(jax.random.key(0), SyntheticConfig(
        n_sensors=6, train_len=48, val_len=24, test_len=48)))
    try:
        sh_eng.run("hfl-selective", odd, (0,), odd_ds)
    except ValueError as e:
        assert "multiple of the device count" in str(e), e
    else:
        raise AssertionError("shard_clients accepted 6 sensors on 4 devices")
    print("SHARD_MAP_EQUIVALENCE_OK")
""")


def test_shard_map_matches_single_device():
    """Client-sharded round loop == single-device, on a forced 4-device
    CPU mesh.  Runs in a subprocess because the XLA device-count flag must
    be set before jax initialises."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 "
        + env.get("XLA_FLAGS", "")
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _SHMAP_SCRIPT],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "SHARD_MAP_EQUIVALENCE_OK" in proc.stdout
