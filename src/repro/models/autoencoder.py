"""The paper's anomaly-detection autoencoder (Table II: 32-16-8-16-32).

A symmetric fully-connected AE with tanh activations, ~1 352 parameters at
D=32.  Written as explicit init/apply functions (no flax) so per-client
parameter stacks vmap cleanly in the federated round.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Params = Any


def init(key: jax.Array, feature_dim: int = 32,
         hidden: tuple[int, ...] = (16, 8, 16)) -> Params:
    """Glorot-initialised MLP autoencoder parameters."""
    dims = (feature_dim, *hidden, feature_dim)
    params = []
    keys = jax.random.split(key, len(dims) - 1)
    for k, (a, b) in zip(keys, zip(dims[:-1], dims[1:])):
        scale = jnp.sqrt(2.0 / (a + b))
        params.append(
            {"w": scale * jax.random.normal(k, (a, b)), "b": jnp.zeros((b,))}
        )
    return params


def apply(params: Params, x: jax.Array) -> jax.Array:
    """Forward pass; tanh on hidden layers, linear output."""
    h = x
    for i, layer in enumerate(params):
        h = h @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            h = jnp.tanh(h)
    return h


def loss(params: Params, batch: jax.Array) -> jax.Array:
    """Mean squared reconstruction error (paper Eq. 9/10)."""
    recon = apply(params, batch)
    return jnp.mean(jnp.sum(jnp.square(batch - recon), axis=-1))


def param_count(feature_dim: int = 32, hidden: tuple[int, ...] = (16, 8, 16)) -> int:
    dims = (feature_dim, *hidden, feature_dim)
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def loss_and_stats(params: Params, batch: jax.Array):
    value = loss(params, batch)
    return value, {"loss": value}


def errors(params: Params, x: jax.Array) -> jax.Array:
    """Squared-L2 reconstruction error per row (paper Sec. V-D)."""
    return jnp.sum(jnp.square(x - apply(params, x)), axis=-1)


def forward_flops(params: Params) -> int:
    """Multiply-adds of one row's forward pass, two operations each."""
    return sum(2 * layer["w"].shape[0] * layer["w"].shape[1] for layer in params)


def widths(params: Params) -> tuple[int, ...]:
    """(D, *hidden, D) of MLP parameters (or of their shapes)."""
    return (params[0]["w"].shape[0], *(layer["w"].shape[1] for layer in params))


def detector(hidden: tuple[int, ...] = (16, 8, 16)):
    """The paper autoencoder as a row detector (``models/detector``)."""
    from repro.models.detector import Detector

    return Detector(
        name="autoencoder",
        init=lambda key, dim: init(key, dim, hidden),
        loss=loss_and_stats,
        score=errors,
        forward_flops=forward_flops,
        fusable=True,
    )
