"""Tests for the fused compress-and-aggregate path.

Covers parity of the fused op with a per-client ``kernels/ref`` oracle
followed by ``fog_aggregate`` (random cluster assignments, zero-weight
non-participants, the n < BLOCK_ELEMS padding edge), Pallas-interpret vs
oracle parity over the kernel grid, the round loop against the same
oracle, and shard_map-vs-single-device equivalence on a forced
multi-device CPU mesh (subprocess, since XLA device flags must be set
before jax initialises).
"""
import functools
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
from repro.kernels import fused_agg, ops, ref

N_FOG = 4
SIZES = [64, 1024, 1352, 4096, 8192 + 17, 65536]
KFRACS = [0.02, 0.05, 0.25]


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


@functools.partial(jax.jit, static_argnames="quantize")
def _ref_blocks(deltas, err, k, quantize):
    """Each client through ``kernels/ref``: EF block Top-K at ``k`` per
    block (+ the int8 round trip).  Returns (recon, new_err), both (N, d)."""
    n, d = deltas.shape
    nb = max(1, -(-d // ops.BLOCK_ELEMS))

    def blocks(a):
        return jnp.pad(a, ((0, 0), (0, nb * ops.BLOCK_ELEMS - d))).reshape(n, nb, -1)

    if quantize:
        q, scale, new_err = jax.vmap(lambda x, e: ref.compress_ref(x, e, k))(
            blocks(deltas), blocks(err))
        recon = ref.dequant8_ref(q, scale)
    else:
        recon, new_err = jax.vmap(
            lambda x, e: ref.blockwise_topk_ef_ref(x, e, k))(blocks(deltas), blocks(err))
    return recon.reshape(n, -1)[:, :d], new_err.reshape(n, -1)[:, :d]


def _ref_compress(deltas, err, cfg):
    """Per-client compression by the oracle: ``kernels/ref`` for blockwise
    configs, the jnp global Top-K (it has no kernel) otherwise."""
    if cfg.mode == "global":
        return jax.vmap(lambda d_, e_: comp.compress_update(d_, e_, cfg))(deltas, err)
    k_frac = comp.blockwise_k_frac(deltas.shape[1], cfg.rho_s)
    k = max(1, int(round(k_frac * ops.BLOCK_ELEMS)))
    return _ref_blocks(deltas, err, k, cfg.quant_bits < 32)


def _oracle(deltas, err, fog_id, weights, cfg):
    recon, new_err = _ref_compress(deltas, err, cfg)
    fog_up, fog_w = agg.fog_aggregate(recon, fog_id, weights, N_FOG)
    return fog_up, fog_w, new_err


def _ref_accumulate(deltas, err, fog_id, weights, n_fog, cfg, chunk=None):
    """``agg.compress_and_accumulate``'s contract from the oracle: per-client
    compression, then the weighted segment-sums."""
    recon, new_err = _ref_compress(deltas, err, cfg)
    fog_sum = jax.ops.segment_sum(recon * weights[:, None], fog_id, num_segments=n_fog)
    fog_weight = jax.ops.segment_sum(weights, fog_id, num_segments=n_fog)
    return fog_sum, fog_weight, new_err


@pytest.mark.parametrize(
    "d",
    [
        1352,        # n < BLOCK_ELEMS: single padded tile (paper autoencoder)
        8192,        # exactly one tile
        20000,       # three tiles with a partial tail
    ],
)
def test_fused_blockwise_matches_unfused_pipeline(d):
    """compress_and_aggregate == per-client kernels/ref compression +
    fog_aggregate to float tolerance on random cluster assignments."""
    deltas, err, fog_id, weights = _inputs(11, d)
    cfg = comp.CompressorConfig(rho_s=0.05, quant_bits=8, mode="blockwise")
    fog_up, fog_w, new_err = agg.compress_and_aggregate(
        deltas, err, fog_id, weights, N_FOG, cfg
    )
    ref_up, ref_w, ref_err = _oracle(deltas, err, fog_id, weights, cfg)
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
    ref_up, ref_w, ref_err = _oracle(deltas, err, fog_id, weights, cfg)
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
    ref_up, _, ref_err = _oracle(deltas, err, fog_id, weights, cfg)
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


def _grid():
    """The kernel grid, one client per fog; where it meets the shared-fog
    grid (k_frac 0.05 at 1352 / 8209 / 65536) six clients share four fogs
    at random weights, some zero."""
    for d in SIZES:
        for k_frac in KFRACS:
            for quantize in (False, True):
                shared = k_frac == 0.05 and d in (1352, 8192 + 17, 65536)
                yield pytest.param(d, k_frac, quantize, shared,
                                   id=f"{d}-{k_frac}-{'q8' if quantize else 'topk'}"
                                      f"{'-shared' if shared else ''}")


@pytest.mark.parametrize("d,k_frac,quantize,shared", list(_grid()))
def test_pallas_interpret_matches_ref(d, k_frac, quantize, shared):
    """The fused kernel body (interpret mode) and the ops oracle must agree
    with kernels/ref applied client by client — same bisection threshold
    and int8 rules — and sum each client into its fog at its weight."""
    if shared:
        deltas, err, fog_id, weights = _inputs(6, d, seed=d)
        n_fog = N_FOG
    else:
        deltas, err, _, _ = _inputs(1, d, seed=d)
        fog_id, weights, n_fog = jnp.zeros((1,), jnp.int32), jnp.ones((1,)), 1
    k = max(1, int(round(k_frac * ops.BLOCK_ELEMS)))
    recon, ne_o = _ref_blocks(deltas, err, k, quantize)
    fs_o = jax.ops.segment_sum(recon * weights[:, None], fog_id, num_segments=n_fog)
    for use_pallas in (True, False):
        fs, ne = ops.compress_aggregate(
            deltas, err, fog_id, weights, n_fog, k_frac, quantize=quantize,
            use_pallas=use_pallas, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(fs), np.asarray(fs_o), rtol=1e-5, atol=1e-4
        )
        np.testing.assert_allclose(np.asarray(ne), np.asarray(ne_o), atol=1e-5)


@pytest.mark.parametrize("d", [1352, 20000])
@pytest.mark.parametrize("rho_s", [1.0, 2.0])
def test_dense_blockwise_is_quantise_only(d, rho_s):
    """A blockwise config at rho_s >= 1 runs the fused kernel keeping every
    coordinate: kernels/ref's int8 round trip at a whole block."""
    deltas, err, fog_id, weights = _inputs(5, d, seed=7)
    recon, ref_err = _ref_blocks(deltas, err, ops.BLOCK_ELEMS, quantize=True)
    ref_up, ref_w = agg.fog_aggregate(recon, fog_id, weights, N_FOG)
    for use_pallas in (True, False):
        cfg = comp.CompressorConfig(rho_s=rho_s, quant_bits=8, mode="blockwise",
                                    use_pallas=use_pallas, interpret=True)
        assert cfg.enabled and not cfg.is_sparse
        fog_up, fog_w, new_err = agg.compress_and_aggregate(
            deltas, err, fog_id, weights, N_FOG, cfg)
        np.testing.assert_allclose(np.asarray(fog_w), np.asarray(ref_w), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(fog_up), np.asarray(ref_up), rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(np.asarray(new_err), np.asarray(ref_err), atol=1e-6)


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


def test_round_loop_fused_matches_unfused(monkeypatch):
    """End-to-end: hfl.train with the fused kernel == the same rounds with
    compression and fog sums done client by client by kernels/ref."""
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
    monkeypatch.setattr(agg, "compress_and_accumulate", _ref_accumulate)
    p2, m2 = hfl.train(jax.random.key(2), params0, ae.loss, ds, cfg)
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
