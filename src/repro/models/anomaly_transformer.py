"""Anomaly Transformer (Xu, Wu, Wang, Long, ICLR 2022, arXiv 2110.02642) as a
per-sensor federated detector.

The model reconstructs a window x of L rows of D features:

* embedding: a circular-padded Conv1d (kernel 3, no bias) from D to
  d_model, plus the fixed sinusoidal position table;
* ``e_layers`` encoder layers, each Anomaly-Attention with ``n_heads``
  heads of d_model / n_heads, residual and LayerNorm, then a feed-forward
  block of two 1x1 convolutions (d_model -> d_ff -> d_model, GELU),
  residual and LayerNorm.  Per head, Q, K, V = x W_Q, x W_K, x W_V and a
  scale sigma = x W_sigma; sigma <- 3^(sigmoid(5 sigma) + 1e-5) - 1; the
  series association is S = softmax(Q K^T / sqrt(d_head)), the prior
  association P_ij = N(|j - i|; 0, sigma_i) normalised by its row sum; the
  head's output is S V;
* a final LayerNorm and a Linear layer d_model -> D.

Association discrepancy: AssDis_i = mean over layers and heads of
KL(P_i || S_i) + KL(S_i || P_i), with 1e-4 inside both logs.  Training is
the minimax strategy: with rec = MSE(x, x_hat) and sg the stop-gradient,
the gradient is that of (rec - k AssDis(sg P, S)) plus that of
(rec + k AssDis(P, sg S)); here it is taken as one backward pass of their
sum.  The reported loss is rec - k AssDis, the released code's logged
loss.  The anomaly score of position i is softmax over the window's
positions of (-50 AssDis_i), times that position's squared error (mean
over features).

Departures from the paper and the released code (``thuml/Anomaly-Transformer``):

* local SGD (or FedProx) inside the federated round, not Adam;
* seeded Glorot-normal weights (biases 0, LayerNorm gain 1), not PyTorch's
  default initialisers;
* the unused ``AttentionLayer.norm`` of the released code is left out
  (it holds parameters but is never applied), so d = 4,825,150 at the
  published SMD widths;
* the score averages the association discrepancy over layers, as
  training does, where the released test code sums it over layers;
* the threshold is the 99th percentile of the validation scores (Eq. 32
  of the federated paper), where the released code takes the
  ``anormly_ratio`` quantile over train and test scores; F1 is point-wise,
  without the released code's point adjustment;
* dropout is 0, as published for SMD, so it is not implemented.

The plain float32 reference is ``kernels/ref.anomaly_transformer_window_ref``
with ``anomaly_transformer_grads_ref`` for the minimax step.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

Params = Any
KL_EPS = 1e-4
SCORE_TEMPERATURE = 50.0
LN_EPS = 1e-5


@dataclasses.dataclass(frozen=True)
class ATConfig:
    """Widths of the model (the published SMD setting by default)."""

    win_size: int = 100
    d_model: int = 512
    n_heads: int = 8
    e_layers: int = 3
    d_ff: int = 512
    k: float = 3.0   # lambda of the minimax objective


def _glorot(key, shape, fan_in, fan_out):
    return math.sqrt(2.0 / (fan_in + fan_out)) * jax.random.normal(key, shape)


def init(key: jax.Array, feature_dim: int, cfg: ATConfig = ATConfig()) -> Params:
    """Seeded Glorot-normal weights, zero biases, unit LayerNorm gains.  The
    encoder layers' leaves are stacked along a leading layer axis."""
    dm, h, ff = cfg.d_model, cfg.n_heads, cfg.d_ff
    k_embed, k_proj, *k_layers = jax.random.split(key, 2 + cfg.e_layers)
    ones, zeros = jnp.ones((dm,)), jnp.zeros((dm,))
    layers = []
    for kl in k_layers:
        kq, kk, kv, ko, ks, k1, k2 = jax.random.split(kl, 7)
        layers.append({
            "wq": _glorot(kq, (dm, dm), dm, dm), "bq": zeros,
            "wk": _glorot(kk, (dm, dm), dm, dm), "bk": zeros,
            "wv": _glorot(kv, (dm, dm), dm, dm), "bv": zeros,
            "wo": _glorot(ko, (dm, dm), dm, dm), "bo": zeros,
            "ws": _glorot(ks, (dm, h), dm, h), "bs": jnp.zeros((h,)),
            "ln1_g": ones, "ln1_b": zeros,
            "w1": _glorot(k1, (dm, ff), dm, ff), "b1": jnp.zeros((ff,)),
            "w2": _glorot(k2, (ff, dm), ff, dm), "b2": zeros,
            "ln2_g": ones, "ln2_b": zeros,
        })
    return {
        "embed": _glorot(k_embed, (3, feature_dim, dm), 3 * feature_dim, dm),
        "layers": jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers),
        "norm_g": ones, "norm_b": zeros,
        "proj_w": _glorot(k_proj, (dm, feature_dim), dm, feature_dim),
        "proj_b": jnp.zeros((feature_dim,)),
    }


def position_table(length: int, d_model: int) -> jax.Array:
    """The fixed sinusoidal table: sin on even, cos on odd channels."""
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d_model, 2, dtype=jnp.float32)
                  * -(math.log(10000.0) / d_model))
    pe = jnp.zeros((length, d_model))
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    return pe.at[:, 1::2].set(jnp.cos(pos * div))


def _layer_norm(x, g, b):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _embed(w, x):
    """Circular Conv1d, kernel 3: out_t = W0 x_{t-1} + W1 x_t + W2 x_{t+1}."""
    length = x.shape[1]
    xp = jnp.concatenate([x[:, -1:], x, x[:, :1]], axis=1)
    out = sum(jnp.einsum("bld,dm->blm", xp[:, i:i + length], w[i]) for i in range(3))
    return out + position_table(length, w.shape[-1])


def _anomaly_attention(lp, x, n_heads):
    """One layer's Anomaly-Attention: (output, S, P), S and P (B, H, L, L)."""
    b, length, dm = x.shape
    e = dm // n_heads
    q = (x @ lp["wq"] + lp["bq"]).reshape(b, length, n_heads, e)
    k = (x @ lp["wk"] + lp["bk"]).reshape(b, length, n_heads, e)
    v = (x @ lp["wv"] + lp["bv"]).reshape(b, length, n_heads, e)
    scores = jnp.einsum("blhe,bshe->bhls", q, k) / math.sqrt(e)
    series = jax.nn.softmax(scores, axis=-1)
    sigma = jnp.swapaxes(x @ lp["ws"] + lp["bs"], 1, 2)          # (B, H, L)
    sigma = jnp.power(3.0, jax.nn.sigmoid(5.0 * sigma) + 1e-5) - 1.0
    pos = jnp.arange(length, dtype=jnp.float32)
    dist = jnp.abs(pos[:, None] - pos[None, :])
    s = sigma[..., None]
    prior = jnp.exp(-jnp.square(dist) / (2.0 * jnp.square(s))) / (math.sqrt(2.0 * math.pi) * s)
    prior = prior / jnp.sum(prior, axis=-1, keepdims=True)
    out = jnp.einsum("bhls,bshe->blhe", series, v).reshape(b, length, dm)
    return out @ lp["wo"] + lp["bo"], series, prior


def forward(params: Params, x: jax.Array, cfg: ATConfig = ATConfig()):
    """x (B, L, D) -> (x_hat (B, L, D), [S per layer], [P per layer]), S and
    P (B, H, L, L).  The layers are unrolled: a scan over them keeps about
    half as much again of each layer's intermediates for the backward pass
    (a 14.6 GB against a 9.2 GB compiled peak at the published widths,
    8 clients x 32 windows a step, on a v5e)."""
    with jax.named_scope("at.embed"):
        h = _embed(params["embed"], x)
    series, priors = [], []
    for i in range(params["layers"]["wq"].shape[0]):
        lp = {name: leaf[i] for name, leaf in params["layers"].items()}
        with jax.named_scope("at.anomaly_attention"):
            a, s, p = _anomaly_attention(lp, h, cfg.n_heads)
            h = _layer_norm(h + a, lp["ln1_g"], lp["ln1_b"])
        with jax.named_scope("at.ffn"):
            y = jax.nn.gelu(h @ lp["w1"] + lp["b1"], approximate=False) @ lp["w2"] + lp["b2"]
            h = _layer_norm(h + y, lp["ln2_g"], lp["ln2_b"])
        series.append(s)
        priors.append(p)
    h = _layer_norm(h, params["norm_g"], params["norm_b"])
    return h @ params["proj_w"] + params["proj_b"], series, priors


def _kl(p, q):
    """KL(p || q) over the last axis with 1e-4 in both logs, averaged over
    heads: (..., H, L, L) -> (..., L)."""
    kl = jnp.sum(p * (jnp.log(p + KL_EPS) - jnp.log(q + KL_EPS)), axis=-1)
    return jnp.mean(kl, axis=-2)


def association_discrepancy(series, priors) -> jax.Array:
    """AssDis per position, (B, L): mean over layers and heads of
    KL(P || S) + KL(S || P)."""
    with jax.named_scope("at.assdis"):
        return sum(_kl(p, s) + _kl(s, p) for s, p in zip(series, priors)) / len(series)


def loss(params: Params, x: jax.Array, cfg: ATConfig = ATConfig()):
    """The minimax objective whose gradient is the two phases' sum, and the
    stats ``loss`` (rec - k AssDis) and ``assdis`` (its batch mean)."""
    x_hat, series, priors = forward(params, x, cfg)
    rec = jnp.mean(jnp.square(x_hat - x))
    sg = jax.lax.stop_gradient
    with jax.named_scope("at.assdis"):
        maximise = sum(jnp.mean(_kl(s, sg(p)) + _kl(sg(p), s))
                       for s, p in zip(series, priors)) / len(series)
        minimise = sum(jnp.mean(_kl(p, sg(s)) + _kl(sg(s), p))
                       for s, p in zip(series, priors)) / len(series)
    objective = (rec - cfg.k * maximise) + (rec + cfg.k * minimise)
    assdis = sg(maximise)
    return objective, {"loss": sg(rec) - cfg.k * assdis, "assdis": assdis}


def score(params: Params, x: jax.Array, cfg: ATConfig = ATConfig()) -> jax.Array:
    """Anomaly score of every position of the windows x (B, L, D) -> (B, L)."""
    x_hat, series, priors = forward(params, x, cfg)
    err = jnp.mean(jnp.square(x - x_hat), axis=-1)
    weight = jax.nn.softmax(-SCORE_TEMPERATURE * association_discrepancy(series, priors),
                            axis=-1)
    return weight * err


def forward_flops(feature_dim: int, cfg: ATConfig = ATConfig()) -> int:
    """Multiply-adds of one window's forward pass, two operations each: the
    embedding, per layer the Q/K/V/O and sigma projections, QK^T and SV,
    the feed-forward block, and the output projection."""
    length, dm, h, ff = cfg.win_size, cfg.d_model, cfg.n_heads, cfg.d_ff
    embed = length * 3 * feature_dim * dm
    layer = (4 * length * dm * dm + length * dm * h + 2 * length * length * dm
             + 2 * length * dm * ff)
    return 2 * (embed + cfg.e_layers * layer + length * dm * feature_dim)


def detector(cfg: ATConfig = ATConfig()):
    """The Anomaly Transformer as a window detector (``models/detector``)."""
    from repro.models.detector import Detector

    return Detector(
        name="anomaly_transformer",
        init=lambda key, dim: init(key, dim, cfg),
        loss=lambda params, x: loss(params, x, cfg),
        score=lambda params, x: score(params, x, cfg),
        forward_flops=lambda params: forward_flops(params["proj_b"].shape[0], cfg),
        window=cfg.win_size,
    )
