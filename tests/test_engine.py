"""Tests for the batched multi-deployment engine (repro.engine)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as eng_mod
from repro.core import compression as comp
from repro.data.synthetic import SyntheticConfig, generate, normalize
from repro.launch import experiment as exp


def _make_ds(seed: int):
    cfg = SyntheticConfig(n_sensors=12, train_len=48, val_len=24, test_len=48)
    return normalize(generate(jax.random.key(seed), cfg))


def _small_cfg(**kw):
    kw.setdefault("rounds", 3)
    kw.setdefault("local_epochs", 1)
    return exp.make_config(n_sensors=12, n_fog=3, **kw)


SEEDS = (0, 1, 2)


def test_batched_run_matches_sequential():
    """Engine.run over 3 seeds == three sequential hfl.train pipelines.

    Column 0 of the trial grid uses exactly ``jax.random.key(seed)``, so
    the batched program must reproduce ``experiment.run_method`` on the
    engine-resolved config to float tolerance (vmap only reassociates)."""
    eng = eng_mod.Engine()
    cfg = _small_cfg()
    run = eng.run("hfl-selective", cfg, SEEDS, _make_ds)
    assert np.asarray(run.f1).shape == (3, 1)

    rcfg = eng.resolve_config(cfg)
    for i, s in enumerate(SEEDS):
        ref = exp.run_method("hfl-selective", _make_ds(s), rcfg, seed=s)
        np.testing.assert_allclose(
            float(run["e_total"][i, 0]), ref.e_total, rtol=1e-5
        )
        np.testing.assert_allclose(float(run.f1[i, 0]), ref.f1, atol=1e-3)
        np.testing.assert_allclose(
            np.asarray(run.losses[i, 0]), np.asarray(ref.losses), rtol=1e-4
        )


def test_batched_run_flat_family_matches_sequential():
    eng = eng_mod.Engine()
    cfg = _small_cfg()
    run = eng.run("fedprox", cfg, SEEDS, _make_ds)
    rcfg = eng.resolve_config(cfg)
    for i, s in enumerate(SEEDS):
        ref = exp.run_method("fedprox", _make_ds(s), rcfg, seed=s)
        np.testing.assert_allclose(
            float(run["e_total"][i, 0]), ref.e_total, rtol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(run.losses[i, 0]), np.asarray(ref.losses), rtol=1e-4
        )


def test_batched_audit_matches_sequential():
    eng = eng_mod.Engine()
    cfg = _small_cfg(rounds=4)
    audit = eng.audit("hfl-nearest", cfg, SEEDS)
    rcfg = eng.resolve_config(cfg)
    for i, s in enumerate(SEEDS):
        ref = exp.audit_method("hfl-nearest", rcfg, seed=s)
        for k in ("e_s2f", "e_f2f", "e_f2g", "e_total", "participation"):
            np.testing.assert_allclose(
                float(audit[k][i, 0]), ref[k], rtol=1e-5, atol=1e-7
            )


def test_program_cache_reuses_compilations():
    eng = eng_mod.Engine()
    cfg = _small_cfg()
    r1 = eng.run("hfl-nocoop", cfg, (0, 1), _make_ds)
    r2 = eng.run("hfl-nocoop", cfg, (0, 1), _make_ds)
    assert r1.fresh_compile and not r2.fresh_compile
    assert eng.compile_count == 1
    np.testing.assert_array_equal(np.asarray(r1.f1), np.asarray(r2.f1))
    log = eng.take_log()
    assert [e["fresh_compile"] for e in log] == [True, False]
    assert eng.take_log() == []


def test_compiled_returns_the_latest_calls_programs():
    """``compiled()`` hands back the programs the latest call executed: one
    for a run, one per shape-class for a sweep.  A bare SensorDataset (a
    named tuple) is one shared sweep dataset, not a per-cell sequence."""
    eng = eng_mod.Engine()
    cfg = _small_cfg(rounds=2)
    eng.run("hfl-nocoop", cfg, (0,), _make_ds)
    (prog,) = eng.compiled()
    assert "ENTRY" in prog.as_text()
    cells = [cfg.replace(compressor=cfg.compressor.replace(rho_s=r))
             for r in (0.05, 0.1)]
    sw = eng.sweep("hfl-nocoop", cells, (0,), _make_ds(0))
    assert len(eng.compiled()) == sw.n_classes >= 1


def test_federated_cli_trains_through_engine():
    """The ``federated`` launcher trains through Engine, so it reports the
    engine-resolved backend (the jnp oracles on the CPU) and the metrics of
    the sequential pipeline on the engine-resolved config."""
    import argparse

    from repro.launch import train

    args = argparse.Namespace(
        method="hfl-selective", sensors=8, fog=2, rounds=2, local_epochs=1,
        lr=0.01, dirichlet_alpha=1.0, seed=0,
    )
    out = train.run_federated(args)
    assert out["local_solver"] == "ref" and "[ref]" in out["compressor"]
    cfg = exp.make_config(n_sensors=8, n_fog=2, rounds=2, local_epochs=1,
                          lr=0.01)
    ds = normalize(generate(jax.random.key(0), SyntheticConfig(
        n_sensors=8, dirichlet_alpha=1.0)))
    ref = exp.run_method("hfl-selective", ds,
                         eng_mod.Engine().resolve_config(cfg), seed=0)
    np.testing.assert_allclose(out["f1"], ref.f1, atol=1e-3)
    np.testing.assert_allclose(out["energy_j"]["total"], ref.e_total,
                               rtol=1e-5)
    np.testing.assert_allclose(out["final_loss"], ref.losses[-1], rtol=1e-4)


def test_deployment_axis_varies_topology():
    """n_deployments adds an independent-deployment column per seed."""
    eng = eng_mod.Engine()
    cfg = _small_cfg(rounds=2)
    audit = eng.audit("hfl-selective", cfg, (0,), n_deployments=3)
    e = np.ravel(np.asarray(audit["e_total"]))
    assert e.shape == (3,)
    assert len(np.unique(e)) == 3  # distinct deployment realisations


def test_engine_resolves_global_compressor_to_blockwise_kernels():
    eng = eng_mod.Engine()
    cc = eng.resolve_compressor(comp.CompressorConfig(rho_s=0.05, quant_bits=8))
    assert cc.mode == "blockwise"
    assert cc.use_pallas == eng_mod.default_use_pallas()
    # Dense / disabled configs are left alone.
    dense = comp.CompressorConfig(rho_s=1.0, quant_bits=32)
    assert eng.resolve_compressor(dense) == dense
    keep = eng_mod.Engine(compressor="keep")
    g = comp.CompressorConfig(rho_s=0.05, quant_bits=8)
    assert keep.resolve_compressor(g) == g


def test_pallas_vs_ref_parity_inside_batched_round():
    """A batched round with the Pallas (interpret) compressor must match
    the kernels/ref.py oracle path — threshold bisection and int8 rules
    are specified to agree exactly."""
    eng = eng_mod.Engine(compressor="keep")
    base = _small_cfg(rounds=2)
    cc_pallas = comp.CompressorConfig(
        rho_s=0.05, quant_bits=8, mode="blockwise",
        use_pallas=True, interpret=True,
    )
    cc_ref = cc_pallas.replace(use_pallas=False)
    rp = eng.run("hfl-selective", base.replace(compressor=cc_pallas),
                 (0, 1), _make_ds)
    rr = eng.run("hfl-selective", base.replace(compressor=cc_ref),
                 (0, 1), _make_ds)
    np.testing.assert_allclose(
        np.asarray(rp.losses), np.asarray(rr.losses), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(rp["e_total"]), np.asarray(rr["e_total"]), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(rp.f1), np.asarray(rr.f1), atol=1e-6
    )


@pytest.mark.parametrize(
    "d,rho",
    [
        (1352, 0.05),    # single padded tile (the paper's autoencoder)
        (9000, 0.9),     # two tiles, short tail, high rho: the uniform
                         # per-tile k would exceed the tail's real coords
        (20000, 0.2),    # three tiles, moderate rho
    ],
)
def test_blockwise_rho_matches_global_keep_count(d, rho):
    """The engine's blockwise default keeps ~rho_s * d coordinates of the
    real (unpadded) update — same K as the paper's global semantics, even
    when the flat vector spans multiple kernel tiles with a partial tail."""
    delta = jax.random.normal(jax.random.key(0), (d,))
    err = jnp.zeros((d,))
    cc = comp.CompressorConfig(rho_s=rho, quant_bits=32, mode="blockwise")
    recon, _ = comp.compress_update(delta, err, cc)
    kept = int(jnp.sum(recon != 0))
    target = round(rho * d)
    # Uniform per-tile k cannot hit the target exactly when it doesn't
    # divide evenly across tiles; a couple coords per tile of slack.
    assert abs(kept - target) <= 2 * (-(-d // 8192)), (kept, target)


@pytest.mark.tpu
def test_compiled_pallas_compressor_on_tpu():
    """Compiled (non-interpret) Pallas path — only meaningful on TPU."""
    eng = eng_mod.Engine()
    assert eng_mod.default_use_pallas()
    cfg = _small_cfg(rounds=2)
    run = eng.run("hfl-selective", cfg, (0,), _make_ds)
    assert bool(jnp.all(jnp.isfinite(run.losses)))
