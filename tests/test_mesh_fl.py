"""Tests for the pod-level compressed exchange (core/mesh_fl.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mesh_fl
from repro.launch.mesh import make_mesh


def test_compact_roundtrip_ef_invariant():
    n = 10_000
    flat = jax.random.normal(jax.random.key(0), (n,))
    q, idx, scale = mesh_fl.compress_compact(flat, rho_s=0.05)
    recon = mesh_fl.decompress_compact(q, idx, scale, n)
    # survivors reconstruct within int8 tolerance; dropped coords are zero
    nnz = np.flatnonzero(np.asarray(recon))
    amax = float(jnp.max(jnp.abs(flat)))
    np.testing.assert_allclose(
        np.asarray(recon)[nnz], np.asarray(flat)[nnz], atol=amax / 127.0
    )
    k = max(1, round(0.05 * mesh_fl.BLOCK))
    nb = -(-n // mesh_fl.BLOCK)
    assert len(nnz) <= nb * k


def test_compact_keeps_largest_per_block():
    flat = jnp.zeros((mesh_fl.BLOCK,)).at[7].set(5.0).at[100].set(-3.0)
    q, idx, scale = mesh_fl.compress_compact(flat, rho_s=2 / mesh_fl.BLOCK)
    recon = mesh_fl.decompress_compact(q, idx, scale, mesh_fl.BLOCK)
    assert float(recon[7]) == pytest.approx(5.0, rel=0.02)
    assert float(recon[100]) == pytest.approx(-3.0, rel=0.02)


def test_wire_bytes_much_smaller_than_dense():
    d = 8_030_261_248  # llama3-8b
    wire = mesh_fl.wire_bytes(d, 0.05)
    assert wire < 0.08 * 4 * d  # >12x smaller than dense f32


def test_pod_hfl_step_single_pod_mesh():
    """On a 1-pod mesh the step must run and decrease loss like plain SGD
    with a quantised gradient (mix degenerates to the identity)."""
    from repro import configs
    from repro.models import api

    cfg = configs.get("llama3_8b", reduced=True).replace(learning_rate=1e-2)
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    step = mesh_fl.make_pod_hfl_train_step(cfg, mesh, mode="int8")
    key = jax.random.key(0)
    params = api.init_params(key, cfg)
    err = mesh_fl.init_err(params, n_pods=1)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab_size)}
    with mesh:
        jstep = jax.jit(step)
        losses = []
        for _ in range(3):
            params, err, loss = jstep(params, err, batch)
            losses.append(float(loss))
    assert losses[-1] < losses[0]
    for leaf in jax.tree_util.tree_leaves(params):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
