"""Optimisers for local client training and server-side updates.

Implemented from scratch (no optax dependency): plain SGD, FedProx's
proximal SGD (Li et al., MLSys'20), Adam for the LLM-scale examples, and
the E-epoch local-training drivers used by the federated round (Eq. 12).

The round loops obtain their client phase from :func:`make_client_solver`,
which returns a BATCHED solver (all clients at once).  For the paper
autoencoder (a ``Detector.fusable`` model) it dispatches to the fused
local-train operator (``kernels/ops.local_train``: the whole E-epoch SGD
phase in one VMEM-resident kernel launch, Pallas on TPU / the
``kernels/ref`` oracle elsewhere) — no dense per-client ``(E * nb, bs,
D)`` batch stream materialises.  Other row models take the per-client
``local_sgd`` scan over gathered minibatches, and window detectors
(``models/detector``, e.g. the Anomaly Transformer) train on minibatches
of their stride-1 windows.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

Params = Any
LossFn = Callable[[Params, jax.Array], jax.Array]


def sgd(params: Params, grads: Params, lr: float) -> Params:
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)


def proximal_grad(params: Params, anchor: Params, grads: Params, mu: float) -> Params:
    """grad + mu (theta - theta_anchor): the FedProx proximal term."""
    return jax.tree_util.tree_map(
        lambda g, p, a: g + mu * (p - a), grads, params, anchor
    )


def local_sgd(
    loss_fn: LossFn,
    params: Params,
    batches: jax.Array,
    lr: float,
) -> tuple[Params, jax.Array]:
    """Run SGD over a (nb, bs, ...) batch stream; returns (params, mean loss)."""
    grad_fn = jax.value_and_grad(loss_fn)

    def step(p, batch):
        loss, g = grad_fn(p, batch)
        return sgd(p, g, lr), loss

    params, losses = jax.lax.scan(step, params, batches)
    return params, jnp.mean(losses)


def proximal_local_sgd(
    loss_fn: LossFn,
    params: Params,
    batches: jax.Array,
    lr: float,
    mu: float,
) -> tuple[Params, jax.Array]:
    """FedProx local solver: SGD on F_i(theta) + mu/2 ||theta - theta^t||^2."""
    anchor = params
    grad_fn = jax.value_and_grad(loss_fn)

    def step(p, batch):
        loss, g = grad_fn(p, batch)
        g = proximal_grad(p, anchor, g, mu)
        return sgd(p, g, lr), loss

    params, losses = jax.lax.scan(step, params, batches)
    return params, jnp.mean(losses)


@dataclasses.dataclass(frozen=True)
class LocalTrainConfig:
    """The backend of the fused local-train kernel
    (``kernels/fused_local_train``) in the client phase (Eq. 12):
    ``use_pallas``/``interpret`` pick it exactly like ``CompressorConfig``.
    Whether a model trains in the kernel is the detector's to say
    (``Detector.fusable``); the others take the scan path.
    """

    use_pallas: bool = False
    interpret: bool = True

    def replace(self, **kw: Any) -> "LocalTrainConfig":
        return dataclasses.replace(self, **kw)


def fusable_params(params: Any) -> bool:
    """True when ``params`` is the AE-style MLP the fused kernel handles:
    a list/tuple of ``{"w", "b"}`` layers with chained 2-D weights and an
    output dimension equal to the input dimension (reconstruction)."""
    if not isinstance(params, (list, tuple)) or not params:
        return False
    prev = None
    for layer in params:
        if not isinstance(layer, dict) or set(layer) != {"w", "b"}:
            return False
        w, b = layer["w"], layer["b"]
        if getattr(w, "ndim", 0) != 2 or getattr(b, "ndim", 0) != 1:
            return False
        if b.shape[0] != w.shape[1]:
            return False
        if prev is not None and w.shape[0] != prev:
            return False
        prev = w.shape[1]
    return params[0]["w"].shape[0] == params[-1]["w"].shape[1]


def make_client_solver(
    loss_fn: LossFn | Any,
    *,
    batch_size: int,
    epochs: int,
    lr: float,
    prox_mu: float = 0.0,
    solver: LocalTrainConfig = LocalTrainConfig(),
) -> Callable[[Params, jax.Array, jax.Array], tuple[jax.Array, Any]]:
    """Build the batched client phase used by the round loops.

    ``loss_fn`` is a plain loss function or a
    :class:`repro.models.detector.Detector`.  Returns ``clients_fn(params,
    data (N, T, D), keys (N,)) -> (flat_deltas (N, d), stats)`` where the
    deltas are ``ravel_pytree(theta_i^E - theta^t)`` — ready to chain into
    the fused compress-and-aggregate operator — and ``stats`` is the mean
    minibatch loss (N,) for a plain loss function, or the detector's
    per-client mean stats ``{"loss": (N,), ...}`` for a detector.

    Dispatch happens per call: when the detector is ``fusable`` and the
    params are the AE-style MLP, the whole phase runs as ONE fused
    operator over all clients; a row detector otherwise takes the
    vmapped ``local_sgd`` / ``proximal_local_sgd`` scan over
    gathered minibatches; a window detector (``Detector.window`` = L)
    trains each client on minibatches of its stride-1 windows, ``epochs``
    shuffles of the T - L + 1 window starts with the remainder dropped.
    """
    from repro.data.pipeline import multi_epoch_batches, multi_epoch_indices
    from repro.kernels import ops as kops
    from repro.models.detector import Detector, as_detector

    det = as_detector(loss_fn)
    objective = det.loss

    # STATIC proximal switch: ``prox_mu`` may be a tracer inside a
    # config-axis sweep, where the proximal term always runs (a runtime mu
    # of 0 contributes an exact zero gradient term); a concrete 0 keeps the
    # plain-SGD solver, bit-identical to the historical path.
    use_prox = not (isinstance(prox_mu, (int, float)) and prox_mu == 0.0)

    def scan_path(params, data, keys):
        plain = lambda p, b: objective(p, b)[0]  # noqa: E731

        def one(dd, kk):
            batches = multi_epoch_batches(kk, dd, batch_size, epochs)
            if use_prox:
                p1, loss = proximal_local_sgd(
                    plain, params, batches, lr, prox_mu
                )
            else:
                p1, loss = local_sgd(plain, params, batches, lr)
            delta = jax.tree_util.tree_map(lambda a, b: a - b, p1, params)
            return ravel_pytree(delta)[0], {"loss": loss}

        return jax.vmap(one)(data, keys)

    def window_path(params, data, keys):
        grad_fn = jax.value_and_grad(objective, has_aux=True)
        offsets = jnp.arange(det.window)

        def one(dd, kk):
            idx = multi_epoch_indices(
                kk, det.samples(dd.shape[0]), batch_size, epochs
            )

            def step(p, starts):
                (_, stats), g = grad_fn(p, dd[starts[:, None] + offsets])
                if use_prox:
                    g = proximal_grad(p, params, g, prox_mu)
                return sgd(p, g, lr), stats

            p1, stats = jax.lax.scan(step, params, idx)
            delta = jax.tree_util.tree_map(lambda a, b: a - b, p1, params)
            return ravel_pytree(delta)[0], jax.tree_util.tree_map(jnp.mean, stats)

        return jax.vmap(one)(data, keys)

    def detector_fn(params, data, keys):
        if det.window is not None:
            return window_path(params, data, keys)
        if det.fusable and fusable_params(params):
            window = data.shape[1]
            idx = jax.vmap(
                lambda k: multi_epoch_indices(k, window, batch_size, epochs)
            )(keys)
            deltas, losses = kops.local_train(
                params, data, idx, lr, prox_mu,
                use_pallas=solver.use_pallas, interpret=solver.interpret,
            )
            return deltas, {"loss": losses}
        return scan_path(params, data, keys)

    if isinstance(loss_fn, Detector):
        return detector_fn

    def clients_fn(params, data, keys):
        deltas, stats = detector_fn(params, data, keys)
        return deltas, stats["loss"]

    return clients_fn


class AdamState(NamedTuple):
    mu: Params
    nu: Params
    count: jax.Array


def adam_init(params: Params) -> AdamState:
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return AdamState(zeros, zeros, jnp.zeros((), jnp.int32))


def adam(
    params: Params,
    grads: Params,
    state: AdamState,
    lr: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
) -> tuple[Params, AdamState]:
    count = state.count + 1
    mu = jax.tree_util.tree_map(
        lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads
    )
    nu = jax.tree_util.tree_map(
        lambda v, g: b2 * v + (1 - b2) * jnp.square(g), state.nu, grads
    )
    c = count.astype(jnp.float32)
    mhat_scale = 1.0 / (1.0 - b1**c)
    vhat_scale = 1.0 / (1.0 - b2**c)

    def upd(p, m, v):
        step = lr * (m * mhat_scale) / (jnp.sqrt(v * vhat_scale) + eps)
        if weight_decay:
            step = step + lr * weight_decay * p
        return p - step

    return jax.tree_util.tree_map(upd, params, mu, nu), AdamState(mu, nu, count)
