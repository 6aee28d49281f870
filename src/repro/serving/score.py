"""Fleet-scale fused anomaly scoring — the serving front-end.

The paper's end product is a detection *service*: fog nodes score sensor
telemetry against the autoencoder threshold (Sec. V-D, Eq. 32) that
federated training keeps fresh.  :func:`score` is that hot path: AE
forward, squared-L2 reconstruction error, and threshold compare run as ONE
fused operator (``kernels/fused_score``, jnp oracle
``kernels/ref.fused_score_ref``) over a ``(fleet, window, d)`` telemetry
batch — compiled Pallas on TPU, the oracle on CPU/GPU, mirroring the
compressor dispatch.  The dense reconstruction never materialises in HBM
on the kernel path.  ``core/anomaly.reconstruction_errors`` +
``flag_anomalies`` (on :func:`dequantize_params` for int8 weights) are the
oracle the tests compare it with.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.ops import default_use_pallas


class ScoreResult(NamedTuple):
    """Per-sample scoring output; both leaves share ``x.shape[:-1]``."""

    error: jax.Array   # squared-L2 reconstruction error (f32)
    flag: jax.Array    # anomaly decision err > tau (bool)


def score(
    params: Any,
    x: jax.Array,
    tau: jax.Array | float,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> ScoreResult:
    """Score telemetry ``x`` of shape (..., d) against threshold(s) ``tau``.

    ``tau`` is a scalar or broadcastable to ``x.shape[:-1]`` (per-row
    thresholds — see :func:`fleet_tau` for the per-fog mapping).  Leading
    axes are flattened into one row axis for the kernel and restored on the
    way out, so (fleet, window, d) batches score as a single sweep.
    """
    return _score(kops.fused_score, params, x, tau, use_pallas, interpret)


def _score(kernel, params, x, tau, use_pallas, interpret) -> ScoreResult:
    if use_pallas is None:
        use_pallas = default_use_pallas()
    if interpret is None:
        interpret = not default_use_pallas()
    lead = x.shape[:-1]
    rows = x.reshape(-1, x.shape[-1])
    tau_rows = jnp.broadcast_to(
        jnp.asarray(tau, jnp.float32), lead
    ).reshape(-1)
    err, flag = kernel(
        rows, params, tau_rows, use_pallas=use_pallas, interpret=interpret
    )
    # Non-finite errors (NaN telemetry, poisoned/diverged model) are
    # anomalous by policy: ``NaN > tau`` is False, which would otherwise
    # silently pass the corrupt rows as normal.
    flag = jnp.where(jnp.isfinite(err), flag, True)
    return ScoreResult(err.reshape(lead), flag.reshape(lead))


def quantize_params(params: Any) -> Any:
    """Per-layer, per-output-channel symmetric int8 weight quantisation.

    Each layer ``{"w", "b"}`` becomes ``{"qw" int8, "sw" (1, d_out) f32,
    "b" f32}`` with ``w ≈ qw * sw`` (``sw = amax(|w|, axis=0) / 127``);
    biases stay f32 (they are a rounding error of the weight bytes).
    Reuses the symmetric-amax scheme of ``kernels/ref.quant8_ref`` at
    per-column granularity, which keeps the reconstruction-error shift within
    ~0.5/127 of each column's dynamic range — tight enough that threshold
    flags survive (parity-tested).  This is the opt-in
    ``weight_dtype="int8"`` serving representation; dequantisation happens
    inside the fused score program (:func:`score_q8`)."""
    q = []
    for layer in params:
        w = jnp.asarray(layer["w"], jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
        safe = jnp.where(scale > 0, scale, 1.0)
        qw = jnp.clip(jnp.round(w / safe), -127, 127).astype(jnp.int8)
        qw = jnp.where(scale > 0, qw, jnp.zeros_like(qw))
        q.append({
            "qw": qw,
            "sw": scale.astype(jnp.float32),
            "b": jnp.asarray(layer["b"], jnp.float32),
        })
    return q


def dequantize_params(qparams: Any) -> Any:
    """Materialise f32 ``{"w", "b"}`` layers from :func:`quantize_params`
    output (the tests' oracle uses this; :func:`score_q8` dequantises
    in-program instead)."""
    return [
        {"w": layer["qw"].astype(jnp.float32) * layer["sw"].reshape(1, -1),
         "b": layer["b"]}
        for layer in qparams
    ]


def score_q8(
    qparams: Any,
    x: jax.Array,
    tau: jax.Array | float,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> ScoreResult:
    """:func:`score` over int8-quantised serving weights.

    ``qparams`` comes from :func:`quantize_params`; the score program
    (oracle and Pallas alike) dequantises per output channel inside it, so
    the weight buffers stay int8 end to end."""
    return _score(kops.fused_score_q8, qparams, x, tau, use_pallas, interpret)


def fleet_tau(
    fog_tau: jax.Array,       # (n_fog,) per-fog thresholds
    fog_id: jax.Array,        # (fleet,) int32 fog assignment per sensor
    window: int,
) -> jax.Array:
    """Map per-fog thresholds onto a (fleet, window) row-threshold grid."""
    return jnp.broadcast_to(
        fog_tau[fog_id][:, None], (fog_id.shape[0], window)
    )


def score_fleet(
    params: Any,
    telemetry: jax.Array,          # (fleet, window, d)
    *,
    tau: jax.Array | float | None = None,
    fog_tau: jax.Array | None = None,
    fog_id: jax.Array | None = None,
    **kw: Any,
) -> ScoreResult:
    """Score a fleet batch with either a global or a per-fog threshold.

    Exactly one of ``tau`` (global, Eq. 32) or (``fog_tau``, ``fog_id``)
    must be given; the latter resolves each sensor's rows against its fog
    cluster's streaming threshold (``serving/calibrate``).
    """
    if (tau is None) == (fog_tau is None):
        raise ValueError("pass exactly one of tau or (fog_tau, fog_id)")
    if fog_tau is not None:
        if fog_id is None:
            raise ValueError("fog_tau needs the fog_id sensor assignment")
        tau = fleet_tau(fog_tau, fog_id, telemetry.shape[1])
    return score(params, telemetry, tau, **kw)
