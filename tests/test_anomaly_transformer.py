"""The Anomaly Transformer detector against its plain float32 references, at
a tiny size on the CPU (D 5, L 10, d_model 32, 2 heads, 2 layers; N 8
sensors under 2 fogs).

Tolerances: the program and the references compute the same float32
arithmetic in a different order (batched heads against per-head loops;
one backward pass of the combined objective against two), so values
agree to a few float32 ulps of their magnitude, never bit for bit; each
tolerance below is a small multiple of that.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from repro.core import energy as en  # noqa: E402
from repro.core.compression import CompressorConfig  # noqa: E402
from repro.data.synthetic import SensorDataset  # noqa: E402
from repro.engine import Engine  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.launch import experiment as exp  # noqa: E402
from repro.models import anomaly_transformer as at  # noqa: E402
from repro.models import autoencoder as ae  # noqa: E402
from repro.models.detector import as_detector  # noqa: E402
from repro.optim.sgd import make_client_solver  # noqa: E402

CFG = at.ATConfig(win_size=10, d_model=32, n_heads=2, e_layers=2, d_ff=32, k=3.0)
D, N, N_FOG = 5, 8, 2
DET = at.detector(CFG)


def _params(seed=0):
    return at.init(jax.random.key(seed), D, CFG)


def _windows(n=3, seed=1):
    return jax.random.normal(jax.random.key(seed), (n, CFG.win_size, D))


def test_forward_matches_reference():
    p, x = _params(), _windows()
    x_hat, series, priors = at.forward(p, x, CFG)
    assdis = at.association_discrepancy(series, priors)
    for b in range(x.shape[0]):
        r_hat, r_s, r_p, r_dis = ref.anomaly_transformer_window_ref(p, x[b], CFG.n_heads)
        # outputs of order 1 after a LayerNorm: a few ulps
        np.testing.assert_allclose(x_hat[b], r_hat, rtol=0, atol=1e-5)
        for layer in range(CFG.e_layers):
            # probabilities in [0, 1]
            np.testing.assert_allclose(series[layer][b], r_s[layer], rtol=0, atol=1e-6)
            np.testing.assert_allclose(priors[layer][b], r_p[layer], rtol=0, atol=1e-6)
        # sums of about 2 x L KL terms of order 1
        np.testing.assert_allclose(assdis[b], r_dis, rtol=0, atol=2e-5)


def test_minimax_gradient_matches_two_gradient_sum():
    """One backward pass of the combined stop-gradient objective equals the
    released code's two backward passes, summed."""
    p, x = _params(), _windows()
    (_, stats), g = jax.value_and_grad(lambda q: at.loss(q, x, CFG), has_aux=True)(p)
    r_g, r_loss = ref.anomaly_transformer_grads_ref(p, x, CFG.n_heads, CFG.k)
    got, want = ravel_pytree(g)[0], ravel_pytree(r_g)[0]
    scale = float(jnp.max(jnp.abs(want)))
    # a gradient summed over 3 windows x 2 phases: ulps of the largest entry
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * scale)
    np.testing.assert_allclose(stats["loss"], r_loss, rtol=1e-5)


def test_score_weights_errors_by_association():
    p, x = _params(), _windows()
    x_hat, series, priors = at.forward(p, x, CFG)
    err = jnp.mean((x - x_hat) ** 2, axis=-1)
    weight = jax.nn.softmax(-50.0 * at.association_discrepancy(series, priors), axis=-1)
    np.testing.assert_allclose(at.score(p, x, CFG), weight * err, rtol=1e-6)


def test_published_widths_count():
    """d = 4,825,150 and 1,023,180,800 forward operations a window at the
    SMD setting (D = 38)."""
    shapes = jax.eval_shape(lambda k: at.init(k, 38), jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 4_825_150
    assert at.forward_flops(38) == 1_023_180_800


def test_detector_flops_feed_the_energy_term():
    """The compute term counts the detector's own work: the paper AE as
    before (every row of every epoch, remainder included), the Anomaly
    Transformer over the stride-1 windows it trains (whole minibatches)."""
    params = ae.init(jax.random.key(0), 32, (16, 8, 16))
    assert ae.detector().train_flops(params, 256, 32, 5) == en.autoencoder_flops(
        32, (16, 8, 16), 256, 5)
    wide = ae.init(jax.random.key(0), 32, (24, 12, 24))
    assert ae.detector((24, 12, 24)).train_flops(wide, 60, 32, 2) == en.autoencoder_flops(
        32, (24, 12, 24), 60, 2)
    assert DET.trained_per_round(40, 4, 2) == 2 * (31 // 4) * 4 == 56
    assert DET.train_flops(_params(), 40, 4, 2) == 3 * at.forward_flops(D, CFG) * 56


def test_plain_loss_refuses_non_mlp_params():
    """A plain loss function stands for an MLP detector; counting the work
    of any other parameter tree is refused with a clear error."""
    det = as_detector(lambda p, b: jnp.mean(b))
    with pytest.raises(TypeError, match="MLP parameters"):
        det.train_flops({"w": jnp.zeros((3, 3))}, 40, 4, 1)
    assert det.train_flops(ae.init(jax.random.key(0), 8, (4,)), 10, 4, 1) == (
        3 * 2 * (8 * 4 + 4 * 8) * 10)


def _series(seed=0, t=40):
    return jax.random.normal(jax.random.key(seed), (N, t, D))


def test_chunked_client_deltas_match_unchunked():
    """Training a chunk of clients gives each client the delta it gets when
    all clients train at once."""
    solver = make_client_solver(DET, batch_size=4, epochs=1, lr=0.01)
    p, data = _params(), _series()
    keys = jax.random.split(jax.random.key(3), N)
    d_all, s_all = solver(p, data, keys)
    for start in (0, 3, 5):
        d_c, s_c = solver(p, data[start:start + 3], keys[start:start + 3])
        # per-client arithmetic is the same; only the batch of clients differs
        np.testing.assert_allclose(d_c, d_all[start:start + 3], rtol=0, atol=1e-7)
        np.testing.assert_allclose(s_c["assdis"], s_all["assdis"][start:start + 3], rtol=1e-6)


def _dataset():
    from bench import data

    cfg = dict(n_sensors=N, feature_dim=D, train_len=40, val_len=20, test_len=40)
    ds = data.dataset(jax.random.key(7), cfg)
    return SensorDataset(ds["train"], ds["val"], ds["test"], ds["test_label"],
                         n_samples=jnp.full((N,), 40.0)), ds


def _hfl_cfg(**kw):
    return exp.make_config(
        n_sensors=N, n_fog=N_FOG, rounds=2, local_epochs=1, batch_size=4, lr=0.01,
        compressor=CompressorConfig(rho_s=0.05, quant_bits=8), **kw)


def test_chunked_round_matches_unchunked():
    """Training inside the chunk scan (3 does not divide 8: the last chunk
    is clamped) against training every client first."""
    ds, _ = _dataset()
    one = Engine(detector=DET).run("hfl-selective", _hfl_cfg(), (4,), ds).metrics
    chunked = Engine(detector=DET, client_chunk=3).run(
        "hfl-selective", _hfl_cfg(), (4,), ds).metrics
    for k in ("losses", "assdis", "e_total", "participation"):
        # fog sums re-associate across chunks: float32 accumulation tolerance
        np.testing.assert_allclose(chunked[k], one[k], rtol=1e-5, err_msg=k)
    assert abs(float(chunked["f1"][0, 0]) - float(one["f1"][0, 0])) <= 0.02


def test_engine_run_matches_plain_reference():
    """One ``Engine.run`` of the window detector against the benchmark's
    plain reference of the same job: per-round loss and association
    discrepancy, the parameters' change, F1 and the physics."""
    from bench import check
    from bench.reference import anomaly_transformer as plain

    ds, raw = _dataset()
    run = Engine(detector=DET, client_chunk=3).run(
        "hfl-selective", _hfl_cfg(), (4,), ds, store=_Store())
    cfg = dict(n_sensors=N, n_fog=N_FOG, rounds=2, local_epochs=1, batch_size=4,
               lr=0.01, feature_dim=D, win_size=10, d_model=32, n_heads=2, e_layers=2,
               d_ff=32, k=3.0, rho_s=0.05, quant_bits=8, percentile=99.0,
               e_init_j=500.0)
    want = jax.jit(lambda key: plain.trial(key, raw, cfg))(jax.random.key(4))
    got = {k: np.asarray(v)[0, 0] for k, v in run.metrics.items()}
    # per-round means over clients of float32 losses: accumulation order
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    np.testing.assert_allclose(got["assdis"], want["assdis"], rtol=1e-5)
    leaves = lambda t: [np.asarray(a) for a in jax.tree_util.tree_leaves(t)]  # noqa: E731
    # the change of each leaf over the job: the program picks each block's
    # Top-K by bisection where the reference sorts, and int8 codes near a
    # rounding boundary flip, so a few coordinates of the update differ
    # (a state left unchanged reads 1)
    assert check.change_gap(leaves(_Store.params), leaves(want["final"]),
                            leaves(want["init"])) < 0.02
    assert abs(float(got["f1"]) - float(want["f1"])) <= 1e-6
    # the physics is float32 arithmetic on identical draws
    np.testing.assert_allclose(got["e_total"], want["e_total"], rtol=1e-6)
    np.testing.assert_allclose(got["participation"], want["participation"], rtol=0)


class _Store:
    params = None

    def publish(self, step, params):
        type(self).params = params


def test_window_detector_trains_only_in_the_hierarchical_families():
    ds, _ = _dataset()
    with pytest.raises(ValueError, match="window detector"):
        exp.trial_metrics("fedavg", jax.random.key(0), ds, _hfl_cfg(), detector=DET)


def test_one_argument_chooses_the_model():
    """The model is the detector or the autoencoder's widths, never both."""
    ds, _ = _dataset()
    with pytest.raises(ValueError, match="not both"):
        Engine(hidden=(8, 4, 8), detector=DET)
    with pytest.raises(ValueError, match="not both"):
        exp.trial_metrics("hfl-selective", jax.random.key(0), ds, _hfl_cfg(),
                          hidden=(8, 4, 8), detector=DET)
    assert Engine(hidden=(8, 4, 8)).detector.init(jax.random.key(0), D)[0]["w"].shape == (D, 8)


def test_the_local_train_pack_reads_the_detectors_widths(tmp_path, monkeypatch):
    """``engine.local_train_pack`` counts the pack of the autoencoder the
    engine trains, from its parameters' shapes."""
    from repro import telemetry
    from repro.kernels import ops

    ds, _ = _dataset()
    monkeypatch.setattr(Engine, "resolve_local_solver",
                        lambda self, ls: ls.replace(use_pallas=True, interpret=True))
    eng = Engine(detector=ae.detector((48, 24, 48)))
    telemetry.clear()
    with jax.profiler.trace(str(tmp_path)):
        eng.run("hfl-selective", _hfl_cfg(), (1,), ds)
    assert ops.local_train_pack((D, 48, 24, 48, D)) == 128 // 48 == 2
    np.testing.assert_array_equal(telemetry.records("engine.local_train_pack"), [2])
    telemetry.clear()
