"""Aggregation operators for hierarchical FL (paper Eqs. 13, 15, 16).

Two execution styles, same math:

1. **Vectorised single-program** (the simulator hot path): per-client
   updates are stacked along a leading axis; fog aggregation is a
   ``segment_sum`` over cluster ids, cooperative mixing a gather + convex
   combination, global aggregation a weighted sum.  Everything jits and
   scans.

2. **Mesh-parallel** (the production runtime): clients live on mesh shards;
   fog aggregation is an in-pod reduction over the ``data`` axis and global
   aggregation a cross-pod reduction over the ``pod`` axis — the TPU
   analogue of the sensor->fog (short acoustic hop) vs fog->gateway (long
   hop) split.  See :func:`hierarchical_mean` (used under ``shard_map``).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core import compression as comp
from repro.core.cooperation import CoopDecision
from repro.kernels import ops as kops


def _tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def fog_aggregate(
    updates: Any,            # pytree, leaves (N, ...) — per-client updates
    fog_id: jax.Array,       # (N,) int32
    weights: jax.Array,      # (N,) f32 — n_i, zeroed for non-participants
    n_fog: int,
) -> tuple[Any, jax.Array]:
    """Intra-cluster weighted aggregation (Eq. 13).

    Returns (fog_updates with leaves (M, ...), fog_weight (M,)) where
    fog_updates[m] = sum_{i in C_m} n_i/sum_C n * update_i and fog_weight is
    the total data weight of the cluster (used again in Eq. 16).
    """
    fog_weight = jax.ops.segment_sum(weights, fog_id, num_segments=n_fog)
    denom = jnp.maximum(fog_weight, 1e-12)

    def agg(leaf):
        w = weights.reshape((-1,) + (1,) * (leaf.ndim - 1))
        summed = jax.ops.segment_sum(leaf * w, fog_id, num_segments=n_fog)
        return summed / denom.reshape((-1,) + (1,) * (leaf.ndim - 1))

    return _tree_map(agg, updates), fog_weight


def _chunk_starts(n: int, chunk: int) -> tuple[jax.Array, jax.Array]:
    """(clamped, nominal) chunk-start indices covering a client axis of n.

    Instead of zero-padding N up to a chunk multiple (two full-size input
    copies), the last chunk is CLAMPED to start at ``n - chunk`` and
    re-reads up to ``chunk - 1`` rows of its predecessor.  Re-reading is
    safe because every per-row output (reconstruction, EF update) is a
    deterministic function of that row alone — overlap rows recompute
    bit-identically — while per-fog sums mask the overlap rows' weights to
    zero via the nominal starts.  Requires ``chunk < n`` (the dispatch
    guarantees it).
    """
    n_chunks = -(-n // chunk)
    nominal = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
    return jnp.minimum(nominal, n - chunk), nominal


def _wire_k_frac(d: int, cfg: comp.CompressorConfig):
    """Concrete per-block keep fraction if the sparse wire is usable.

    The wire is shape-bearing (k slots per block), so it needs a concrete
    ``rho_s``; config-axis sweeps trace it and must keep the dense oracle.
    Returns a float, or None when the config doesn't qualify.
    """
    if not (cfg.enabled and cfg.is_sparse and cfg.mode == "blockwise"):
        return None
    k_frac = comp.blockwise_k_frac(d, cfg.rho_s)
    if not isinstance(k_frac, (int, float)):
        return None
    comp.validate_blockwise_bits(cfg.quant_bits)
    return k_frac


def _chunked_compress_and_accumulate(
    deltas, err, fog_id, weights, n_fog: int, cfg, chunk: int
):
    """:func:`client_chunk_scan` over deltas already trained: each chunk
    is a slice of ``deltas``."""
    fog_sum, fog_weight, new_err, _ = client_chunk_scan(
        lambda start: (jax.lax.dynamic_slice_in_dim(deltas, start, chunk), {}),
        err, fog_id, weights, None, n_fog, cfg, chunk,
    )
    return fog_sum, fog_weight, new_err


def client_chunk_scan(
    chunk_deltas, err, fog_id, weights, keep, n_fog: int, cfg, chunk: int
):
    """``lax.scan`` over client chunks carrying the (n_fog, d) buffers.

    ``chunk_deltas(start)`` gives the flat deltas (chunk, d) of clients
    ``start .. start + chunk`` and a pytree of per-client outputs (leaves
    (chunk, ...)); the round loop trains the chunk there, so that only
    the error-feedback state is ever (N, d).  Each scan step then
    compresses and accumulates that chunk, so the transient footprint
    (deltas, blocked tiles, masks, wire slots, training activations) is
    O(chunk) instead of O(N) — the peak high-water mark scales with the
    chunk knob, not the fleet.

    Inside each chunk, a concrete-``rho_s`` sparse blockwise config takes
    the sparse wire (emit + scatter-accumulate, no dense per-chunk
    reconstruction); anything else falls back to the dense per-chunk path
    (still chunk-bounded).  Chunks are addressed with clamped
    ``dynamic_slice`` starts (:func:`_chunk_starts`), and the EF state is
    the scan's carry, updated in place: a client's row is rewritten only
    by its own (nominal) chunk and only where ``keep`` (None: every
    client) holds, so rows of non-participants keep their buffer and the
    rows the clamped last chunk re-reads keep what their chunk wrote.
    Float summation order differs from the unchunked pass, which is why
    the equivalence pins are bitwise only at ``chunk >= N`` (where this
    function is never entered).

    Returns (fog_sum, fog_weight, new_err, outputs) with the per-client
    outputs back in client order (leaves (N, ...)).
    """
    n, d = err.shape
    starts, nominal = _chunk_starts(n, chunk)
    k_frac = _wire_k_frac(d, cfg)

    def body(carry, x):
        fog_sum, fog_weight, err_out = carry
        start, nom = x
        dc, outputs = chunk_deltas(start)
        ec = jax.lax.dynamic_slice_in_dim(err_out, start, chunk)
        fc = jax.lax.dynamic_slice_in_dim(fog_id, start, chunk)
        wc = jax.lax.dynamic_slice_in_dim(weights, start, chunk)
        # Rows the clamped last chunk re-reads were already accumulated;
        # zero their weight so the fog sums count every client once.
        fresh = start + jnp.arange(chunk, dtype=jnp.int32) >= nom
        wc = wc * fresh.astype(wc.dtype)
        if k_frac is not None:
            # Same graceful-degradation guard as the unchunked path.
            finite = jnp.all(jnp.isfinite(dc), axis=-1) & jnp.all(
                jnp.isfinite(ec), axis=-1
            )
            dc = jnp.where(finite[:, None], dc, 0.0)
            wc = wc * finite.astype(wc.dtype)
            part_w = jax.ops.segment_sum(wc, fc, num_segments=n_fog)
            # ``ec`` itself stays: rows not rewritten below keep it as is.
            part, new_err_c = kops.compress_aggregate_wire(
                dc, jnp.where(finite[:, None], ec, 0.0), fc, wc, n_fog, k_frac,
                quantize=cfg.quant_bits < 32,
                use_pallas=cfg.use_pallas,
                interpret=cfg.interpret,
            )
        else:
            part, part_w, new_err_c = compress_and_accumulate(
                dc, ec, fc, wc, n_fog, cfg
            )
        write = fresh
        if keep is not None:
            write = write & jax.lax.dynamic_slice_in_dim(keep, start, chunk)
        err_out = jax.lax.dynamic_update_slice_in_dim(
            err_out, jnp.where(write[:, None], new_err_c, ec), start, 0
        )
        return (fog_sum + part, fog_weight + part_w, err_out), outputs

    carry0 = (
        jnp.zeros((n_fog, d), jnp.float32),
        jnp.zeros((n_fog,), jnp.float32),
        err,
    )
    (fog_sum, fog_weight, new_err), outputs = jax.lax.scan(
        body, carry0, (starts, nominal)
    )
    # Client i's outputs come from its nominal chunk.
    c = jnp.minimum(jnp.arange(n) // chunk, starts.shape[0] - 1)
    at = jnp.arange(n) - starts[c]
    outputs = jax.tree_util.tree_map(lambda o: o[c, at], outputs)
    return fog_sum, fog_weight, new_err, outputs


def compress_and_accumulate(
    deltas: jax.Array,      # (N, d) raw flat client updates
    err: jax.Array,         # (N, d) error-feedback buffers
    fog_id: jax.Array,      # (N,) int32 cluster assignment
    weights: jax.Array,     # (N,) f32, zeroed for non-participants
    n_fog: int,
    cfg: comp.CompressorConfig,
    chunk: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-client compression + UNNORMALISED weighted fog sums (one pass).

    The shard_map round loop psums these partials over the client axis
    before normalising; :func:`compress_and_aggregate` is the single-shard
    wrapper that divides through directly.

    Returns (fog_sum (n_fog, d) = sum_{i in C_m} w_i recon_i,
    fog_weight (n_fog,) = sum_{i in C_m} w_i, new_err (N, d)).

    Graceful degradation: rows carrying any NaN/Inf (a diverging or
    malicious client) are zeroed — delta, EF buffer AND weight — before
    they touch the fog sums, so one poisoned client can never NaN the
    global model.  Always on, independent of the fault layer; a no-op
    (bit-identical ``where(true, x, _)``) for finite inputs.

    ``chunk`` (the resolved ``HFLConfig.client_chunk``) bounds the
    transient memory: ``None`` or ``chunk >= N`` runs the one-shot path
    below UNCHANGED (bit-identical to the pre-chunking code); a smaller
    chunk scans :func:`_chunked_compress_and_accumulate` over client
    chunks.
    """
    if chunk is not None and 0 < chunk < deltas.shape[0]:
        return _chunked_compress_and_accumulate(
            deltas, err, fog_id, weights, n_fog, cfg, chunk
        )
    finite = jnp.all(jnp.isfinite(deltas), axis=-1) & jnp.all(
        jnp.isfinite(err), axis=-1
    )
    deltas = jnp.where(finite[:, None], deltas, 0.0)
    err = jnp.where(finite[:, None], err, 0.0)
    weights = weights * finite.astype(weights.dtype)
    fog_weight = jax.ops.segment_sum(weights, fog_id, num_segments=n_fog)

    if cfg.enabled and cfg.mode == "blockwise":
        # The fused kernel path: EF Top-K + int8 + weighted accumulation
        # directly into the (n_fog, d) buffers — the dense per-client
        # reconstruction never materialises.  A dense rho_s >= 1 keeps
        # every real coordinate (quantise-only).
        comp.validate_blockwise_bits(cfg.quant_bits)
        fog_sum, new_err = kops.compress_aggregate(
            deltas, err, fog_id, weights, n_fog,
            comp.blockwise_k_frac(deltas.shape[1], cfg.rho_s),
            quantize=cfg.quant_bits < 32,
            use_pallas=cfg.use_pallas,
            interpret=cfg.interpret,
        )
        return fog_sum, fog_weight, new_err

    # Compression off or mode="global": per-client reconstruction, then a
    # dense segment-sum.
    if cfg.enabled:
        recon, new_err = jax.vmap(
            lambda d_, e_: comp.compress_update(d_, e_, cfg)
        )(deltas, err)
    else:
        recon, new_err = deltas, err
    fog_sum = jax.ops.segment_sum(
        recon * weights[:, None], fog_id, num_segments=n_fog
    )
    return fog_sum, fog_weight, new_err


def compress_and_aggregate(
    deltas: jax.Array,
    err: jax.Array,
    fog_id: jax.Array,
    weights: jax.Array,
    n_fog: int,
    cfg: comp.CompressorConfig,
    axis: str | None = None,
    chunk: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused sensor-uplink compression + intra-cluster aggregation.

    Eq. 30 (EF compression) and Eq. 13 (weighted fog aggregation) as ONE
    operator: per (client, block), the update is sparsified/quantised and
    its reconstruction accumulated straight into the fog buffers.  This is
    the round loop's hot path; see :mod:`repro.kernels.fused_agg` for the
    single-HBM-pass kernel it dispatches to.

    Under ``shard_map`` pass the client mesh ``axis``: each shard's partial
    fog sums are psum-reduced before normalising (the sensor->fog hop, cf.
    :func:`hierarchical_mean`).  ``chunk`` applies WITHIN the shard's local
    client slice, so chunking composes with ``shard_clients``.

    Returns (fog_update (n_fog, d) — the Eq. 13 weighted cluster means —
    fog_weight (n_fog,), new_err (N, d)).  Empty clusters get zero updates.
    """
    fog_sum, fog_weight, new_err = compress_and_accumulate(
        deltas, err, fog_id, weights, n_fog, cfg, chunk=chunk
    )
    if axis is not None:
        fog_sum = jax.lax.psum(fog_sum, axis)
        fog_weight = jax.lax.psum(fog_weight, axis)
    denom = jnp.maximum(fog_weight, 1e-12)
    return fog_sum / denom[:, None], fog_weight, new_err


def client_compress(
    deltas: jax.Array,      # (N, d) raw flat client updates
    err: jax.Array,         # (N, d) error-feedback buffers
    cfg: comp.CompressorConfig,
    chunk: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-client compression with identity segments, optionally chunked.

    The robust and async paths need each client's dequantised
    reconstruction to stay addressable (the order statistic / the in-flight
    buffer reads them per client), so the output is necessarily (N, d) —
    but the compression TRANSIENTS (blocked tiles, bisection masks, quant
    scratch) need not be: with ``chunk`` set, a ``lax.scan`` emits the
    reconstructions chunk-at-a-time and only O(chunk * d) of scratch is
    live at once.

    ``chunk=None`` / ``chunk >= N`` is the exact legacy call
    (``fog_id = arange(N)``, unit weights — bit-identical); returns
    (recon (N, d), new_err (N, d)).
    """
    n = deltas.shape[0]
    if chunk is None or chunk <= 0 or chunk >= n:
        recon, _, new_err = compress_and_accumulate(
            deltas, err,
            jnp.arange(n, dtype=jnp.int32), jnp.ones((n,), jnp.float32),
            n, cfg,
        )
        return recon, new_err
    d = deltas.shape[1]
    starts, _ = _chunk_starts(n, chunk)

    def body(carry, start):
        recon_out, err_out = carry
        dc = jax.lax.dynamic_slice_in_dim(deltas, start, chunk)
        ec = jax.lax.dynamic_slice_in_dim(err, start, chunk)
        recon_c, _, new_err_c = compress_and_accumulate(
            dc, ec,
            jnp.arange(chunk, dtype=jnp.int32),
            jnp.ones((chunk,), jnp.float32),
            chunk, cfg,
        )
        # Rows the clamped last chunk re-reads recompute bit-identically
        # (per-row determinism), so overwriting them is harmless.
        recon_out = jax.lax.dynamic_update_slice_in_dim(
            recon_out, recon_c, start, 0
        )
        err_out = jax.lax.dynamic_update_slice_in_dim(
            err_out, new_err_c, start, 0
        )
        return (recon_out, err_out), None

    carry0 = (
        jnp.zeros((n, d), deltas.dtype),
        jnp.zeros((n, d), deltas.dtype),
    )
    (recon, new_err), _ = jax.lax.scan(body, carry0, starts)
    return recon, new_err


def robust_compress_and_aggregate(
    deltas: jax.Array,      # (N, d) raw flat client updates
    err: jax.Array,         # (N, d) error-feedback buffers
    fog_id: jax.Array,      # (N,) int32 cluster assignment
    weights: jax.Array,     # (N,) f32, zeroed for non-participants
    n_fog: int,
    cfg: comp.CompressorConfig,
    trim_frac: float | jax.Array,
    mode: str,              # "trimmed" | "median"
    chunk: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Byzantine-robust variant of :func:`compress_and_aggregate`.

    Runs the SAME fused compress path but with per-client segments
    (``fog_id = arange(N)``, unit weights — the async family's trick), so
    each client's dequantised reconstruction stays addressable and the EF
    buffer math is bit-identical to the mean path; the per-fog reduce is
    then the coordinate-wise trimmed mean / median
    (:func:`repro.kernels.ops.robust_aggregate`) instead of the weighted
    sum.  At ``trim_frac == 0`` this reproduces the weighted mean to float
    tolerance (summation order differs).

    Returns (fog_update (n_fog, d) — NORMALISED robust aggregates —
    fog_weight (n_fog,), new_err (N, d)).  ``chunk`` bounds the compress
    transients (see :func:`client_compress`); the (N, d) reconstructions
    themselves are what the order statistic consumes, so they remain.
    """
    recon, new_err = client_compress(deltas, err, cfg, chunk=chunk)
    # The isfinite guard above zeroed poisoned reconstructions; their
    # aggregation weight must vanish too, or a zeroed row would still pull
    # the order statistic toward zero.
    finite = jnp.all(jnp.isfinite(deltas), axis=-1) & jnp.all(
        jnp.isfinite(err), axis=-1
    )
    fog_out, fog_weight = kops.robust_aggregate(
        recon, fog_id, weights * finite.astype(weights.dtype), n_fog,
        trim_frac, mode,
        use_pallas=cfg.use_pallas, interpret=cfg.interpret,
    )
    return fog_out, fog_weight, new_err


def cooperative_mix(fog_models: Any, decision: CoopDecision) -> Any:
    """Cooperative fog mixing (Eq. 15 with K=1 rule family).

    theta~_m = alpha_mm theta_m + alpha_mj theta_j.  Non-cooperating fogs
    have partner=m and weights (1, 0), so this is the identity for them.
    """

    def mix(leaf):
        peer = leaf[decision.partner]
        ws = decision.self_weight.reshape((-1,) + (1,) * (leaf.ndim - 1))
        wp = decision.partner_weight.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return ws * leaf + wp * peer

    return _tree_map(mix, fog_models)


def global_aggregate(
    fog_models: Any,         # pytree, leaves (M, ...)
    fog_weight: jax.Array,   # (M,) — sum of n_i over the cluster
    prev: Any = None,        # carry-through when the whole round is dead
) -> Any:
    """Surface-gateway aggregation (Eq. 16): data-weighted fog average.

    A dead-network round (no active sensor in any cluster) has total weight
    0; the normalised weights then vanish and the weighted sum would wipe
    the model to zeros.  Pass ``prev`` (the current global model, leaves
    matching ``fog_models`` without the leading fog axis) to carry it
    through instead — the round becomes an explicit no-op.
    """
    total = jnp.sum(fog_weight)
    w = fog_weight / jnp.maximum(total, 1e-12)

    def agg(leaf):
        return jnp.tensordot(w, leaf, axes=(0, 0))

    out = _tree_map(agg, fog_models)
    if prev is None:
        return out
    return _tree_map(lambda o, p: jnp.where(total > 0.0, o, p), out, prev)


def weighted_mean(updates: Any, weights: jax.Array, prev: Any = None) -> Any:
    """Flat weighted average over the leading client axis (FedAvg, Eq. 11).

    Same zero-total-weight semantics as :func:`global_aggregate`: with
    ``prev`` given, an all-zero weight vector returns ``prev`` instead of
    collapsing to zeros.  (The flat round loops average *deltas*, where the
    zero default already means "hold the model" — ``prev`` matters when the
    averaged quantity is the model itself.)
    """
    total = jnp.sum(weights)
    w = weights / jnp.maximum(total, 1e-12)

    def agg(leaf):
        return jnp.tensordot(w, leaf, axes=(0, 0))

    out = _tree_map(agg, updates)
    if prev is None:
        return out
    return _tree_map(lambda o, p: jnp.where(total > 0.0, o, p), out, prev)


# ---------------------------------------------------------------------------
# Mesh-parallel hierarchical aggregation (used under shard_map).
# ---------------------------------------------------------------------------

def hierarchical_mean(
    update: Any,
    weight: jax.Array,
    *,
    intra_axis: str = "data",
    inter_axis: str | None = "pod",
) -> Any:
    """Two-level weighted mean: reduce within the pod, then across pods.

    Called from inside ``shard_map`` with per-shard (client) updates.  The
    in-pod reduction is the cheap hop (fog aggregation); the cross-pod
    reduction is the expensive hop (fog->gateway).  With ``inter_axis=None``
    this degenerates to flat FedAvg over ``intra_axis``.
    """
    wsum_local = jax.lax.psum(weight, intra_axis)

    def intra(leaf):
        return jax.lax.psum(leaf * weight, intra_axis) / jnp.maximum(
            wsum_local, 1e-12
        )

    fog_model = _tree_map(intra, update)
    if inter_axis is None:
        return fog_model

    wsum_global = jax.lax.psum(wsum_local, inter_axis)

    def inter(leaf):
        return jax.lax.psum(leaf * wsum_local, inter_axis) / jnp.maximum(
            wsum_global, 1e-12
        )

    return _tree_map(inter, fog_model)


def ring_mix(update: Any, mix_weight: float, axis: str = "pod") -> Any:
    """Gossip mixing with the ring neighbour over ``axis`` — the mesh
    analogue of fog-to-fog cooperation, lowering to collective_permute."""
    n = jax.lax.axis_size(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def mix(leaf):
        peer = jax.lax.ppermute(leaf, axis, perm)
        return (1.0 - mix_weight) * leaf + mix_weight * peer

    return _tree_map(mix, update)
