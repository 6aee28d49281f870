"""Seeded telemetry and weights, made on the device in one jitted call.

The telemetry generator is a copy of the system's synthetic IoUT source
(latent sinusoid + AR(1) modes mixed per sensor by a Dirichlet draw, D =
32 features, spike / ramp / stuck anomaly segments in the test window,
per-sensor z-scoring on the train window), kept here so that the
benchmark's inputs cannot change when the program does.  Everything is a
plain dict of arrays: the drivers hand it to the program in the program's
own container, the reference reads it as is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

N_MODES, LATENT_DIM, NOISE_STD = 5, 4, 0.05
ANOMALY_RATE, ANOMALY_SCALE, DIRICHLET_ALPHA = 0.15, 1.5, 1.0


def _latent(key, length, dim):
    kf, kp, kn = jax.random.split(key, 3)
    t = jnp.arange(length, dtype=jnp.float32)[:, None]
    freq = jax.random.uniform(kf, (dim,), minval=0.01, maxval=0.1)
    phase = jax.random.uniform(kp, (dim,), minval=0.0, maxval=2.0 * jnp.pi)
    noise = jax.random.normal(kn, (length, dim)) * 0.3

    def ar(carry, x):
        y = 0.9 * carry + x
        return y, y

    _, ar_noise = jax.lax.scan(ar, jnp.zeros((dim,)), noise)
    return jnp.sin(2.0 * jnp.pi * freq * t + phase) + 0.2 * ar_noise


def _anomalies(key, x):
    length, d = x.shape
    kseg, ktype, kfeat, kmag = jax.random.split(key, 4)
    n_seg = 3
    seg_len = max(1, int(ANOMALY_RATE * length / n_seg))
    starts = jax.random.randint(kseg, (n_seg,), 0, max(1, length - seg_len))
    pos = jnp.arange(length)
    label = jnp.zeros((length,), bool)
    for s in range(n_seg):
        label = label | ((pos >= starts[s]) & (pos < starts[s] + seg_len))
    feat = jax.random.bernoulli(kfeat, 0.4, (d,))
    kind = jax.random.randint(ktype, (), 0, 3)
    mag = ANOMALY_SCALE * (1.0 + jax.random.uniform(kmag, ()))
    spike = x + mag * feat[None, :] * jnp.sign(jax.random.normal(kmag, x.shape))
    ramp = x + mag * feat[None, :] * jnp.linspace(0.0, 1.0, length)[:, None]
    stuck = jnp.where(feat[None, :], jnp.mean(x, 0, keepdims=True) + mag, x)
    anom = jax.lax.switch(kind, [lambda: spike, lambda: ramp, lambda: stuck])
    return jnp.where(label[:, None], anom, x), label


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def telemetry(key, n, dim, train_len, val_len, test_len):
    """(train, val, test, test_label) for ``n`` sensors, z-scored per
    sensor with the train window's statistics."""
    k_modes, k_mix, k_sensors = jax.random.split(key, 3)
    maps = jax.random.normal(k_modes, (N_MODES, LATENT_DIM, dim)) / jnp.sqrt(LATENT_DIM)
    mix = jax.random.dirichlet(k_mix, jnp.full((N_MODES,), DIRICHLET_ALPHA), (n,))
    total = train_len + val_len + test_len

    def one(key, w):
        kl, kn, ka = jax.random.split(key, 3)
        x = _latent(kl, total, LATENT_DIM) @ jnp.einsum("m,mld->ld", w, maps)
        x = x + NOISE_STD * jax.random.normal(kn, (total, dim))
        train, val = x[:train_len], x[train_len:train_len + val_len]
        test, label = _anomalies(ka, x[train_len + val_len:])
        mean = jnp.mean(train, 0, keepdims=True)
        std = jnp.std(train, 0, keepdims=True) + 1e-6
        return (train - mean) / std, (val - mean) / std, (test - mean) / std, label

    return jax.vmap(one)(jax.random.split(k_sensors, n), mix)


def dataset(key, cfg: dict) -> dict:
    train, val, test, label = telemetry(
        key, cfg["n_sensors"], cfg["feature_dim"], cfg["train_len"],
        cfg["val_len"], cfg["test_len"])
    return {"train": train, "val": val, "test": test, "test_label": label}


@functools.partial(jax.jit, static_argnums=(1,))
def ae_weights(key, dims: tuple[int, ...]):
    """Glorot-normal f32 autoencoder weights as the program serves them:
    a list of ``{"w", "b"}`` layers."""
    keys = jax.random.split(key, len(dims) - 1)
    return [
        {"w": jnp.sqrt(2.0 / (a + b)) * jax.random.normal(k, (a, b)),
         "b": jnp.zeros((b,))}
        for k, (a, b) in zip(keys, zip(dims[:-1], dims[1:]))
    ]
