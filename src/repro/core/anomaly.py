"""Anomaly scoring, threshold calibration, and detection metrics.

Implements the paper's Sec. V-D (99th-percentile global threshold on a
normal-only validation window) plus the two metrics used in evaluation:
point-wise F1 (synthetic study) and point-adjusted F1 (real benchmarks),
the standard segment-generous protocol.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


def reconstruction_errors(
    apply_fn: Callable[[Any, jax.Array], jax.Array],
    params: Any,
    x: jax.Array,
) -> jax.Array:
    """Squared-L2 reconstruction error per sample (paper Sec. V-D)."""
    recon = apply_fn(params, x)
    return jnp.sum(jnp.square(x - recon), axis=-1)


def calibrate_threshold(errors: jax.Array, percentile: float = 99.0) -> jax.Array:
    """Global threshold tau_A = p-th percentile of validation errors (Eq. 32)."""
    return jnp.percentile(errors, percentile)


def flag_anomalies(errors: jax.Array, tau: jax.Array) -> jax.Array:
    """Boolean anomaly decisions: e > tau_A."""
    return errors > tau


class F1Result(NamedTuple):
    f1: jax.Array
    precision: jax.Array
    recall: jax.Array


def pointwise_f1(pred: jax.Array, label: jax.Array) -> F1Result:
    """Point-wise F1 over boolean prediction/label arrays."""
    pred = pred.astype(jnp.float32)
    label = label.astype(jnp.float32)
    tp = jnp.sum(pred * label)
    fp = jnp.sum(pred * (1.0 - label))
    fn = jnp.sum((1.0 - pred) * label)
    precision = tp / jnp.maximum(tp + fp, 1e-12)
    recall = tp / jnp.maximum(tp + fn, 1e-12)
    f1 = 2.0 * precision * recall / jnp.maximum(precision + recall, 1e-12)
    return F1Result(f1, precision, recall)


def point_adjust(pred: jax.Array, label: jax.Array) -> jax.Array:
    """Point-adjusted predictions (PA protocol, paper Sec. VI-F).

    If any point inside a contiguous anomalous segment is detected, the
    whole segment is credited.  Implemented with a forward/backward
    segment-id sweep so it stays jittable.
    """
    label = label.astype(bool)
    pred = pred.astype(bool)
    # Segment id: cumulative count of rising edges, 0 outside segments.
    start = label & ~jnp.concatenate([jnp.array([False]), label[:-1]])
    seg_id = jnp.cumsum(start.astype(jnp.int32)) * label.astype(jnp.int32)
    n_seg = jnp.max(seg_id) + 1
    hit_per_seg = jax.ops.segment_sum(
        (pred & label).astype(jnp.int32),
        seg_id,
        num_segments=pred.shape[0] + 1,
    )
    seg_hit = hit_per_seg[seg_id] > 0
    return jnp.where(label, seg_hit, pred)


def point_adjusted_f1(pred: jax.Array, label: jax.Array) -> F1Result:
    """PA-F1: point-wise F1 on point-adjusted predictions."""
    return pointwise_f1(point_adjust(pred, label), label)


def evaluate_detector(
    apply_fn: Callable[[Any, jax.Array], jax.Array],
    params: Any,
    x_val_normal: jax.Array,
    x_test: jax.Array,
    y_test: jax.Array,
    percentile: float = 99.0,
    point_adjusted: bool = False,
) -> F1Result:
    """Full paper protocol: calibrate on normal-only val, score test, F1.

    ``apply_fn(params, x)`` reconstructs rows; the score of a row is its
    squared-L2 reconstruction error."""
    return evaluate_scores(
        lambda p, x: reconstruction_errors(apply_fn, p, x), params,
        x_val_normal, x_test, y_test,
        percentile=percentile, point_adjusted=point_adjusted,
    )


def evaluate_scores(
    score_fn: Callable[[Any, jax.Array], jax.Array],
    params: Any,
    x_val_normal: jax.Array,
    x_test: jax.Array,
    y_test: jax.Array,
    percentile: float = 99.0,
    point_adjusted: bool = False,
) -> F1Result:
    """The protocol for any per-point score (higher is more anomalous):
    threshold at the ``percentile`` of the normal validation scores
    (Eq. 32), flag test points above it, and score them against the labels
    (point-wise, or point-adjusted)."""
    val_err = score_fn(params, x_val_normal)
    tau = calibrate_threshold(val_err, percentile)
    test_err = score_fn(params, x_test)
    pred = flag_anomalies(test_err, tau)
    if point_adjusted:
        return point_adjusted_f1(pred, y_test)
    return pointwise_f1(pred, y_test)
