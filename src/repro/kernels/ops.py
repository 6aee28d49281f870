"""Public jitted wrappers around the Pallas kernels.

These own layout: flat update vectors are zero-padded to a whole number of
(BLOCK_ROWS x BLOCK_LANES) tiles and reshaped for the kernels; outputs are
un-padded back.  ``use_pallas=False`` routes to the pure-jnp oracle (the
default on the CPU dry-run path, so lowered HLO stays clean for roofline
analysis); ``use_pallas=True`` with ``interpret=True`` exercises the kernel
body on CPU, and on a real TPU ``interpret=False`` compiles it.

Scalar knobs (``k_frac``, ``lr``, ``prox_mu``) are TRACEABLE on the oracle
path: the blockwise selection is threshold-by-bisection against a keep
*count* and SGD uses the rates purely arithmetically, so config-axis
sweeps (``Engine.sweep``) can batch different knob values in one compiled
program.  The Pallas kernels bake those scalars into the kernel body, so
the pallas branch still requires concrete Python numbers — the sweep
driver keeps kernel-bound knobs static per shape-class on TPU.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Iterator

import jax
import jax.numpy as jnp

from repro.kernels import fused_agg as _fa
from repro.kernels import fused_local_train as _flt
from repro.kernels import fused_score as _fs
from repro.kernels import ref as _ref
from repro.kernels import robust_agg as _ra
from repro.kernels import swa_attention as _swa

BLOCK_ELEMS = _fa.BLOCK_ELEMS


def default_use_pallas() -> bool:
    """Compiled Pallas kernels need a real TPU; elsewhere callers fall back
    to the pure-jnp oracle in :mod:`repro.kernels.ref`."""
    return jax.default_backend() == "tpu"


def _pad_blocks_batch(x: jax.Array) -> tuple[jax.Array, int]:
    """Zero-pad (N, d) rows to (N, nb, ROWS, LANES); return original d."""
    n_rows, d = x.shape
    nb = max(1, -(-d // BLOCK_ELEMS))
    padded = jnp.zeros((n_rows, nb * BLOCK_ELEMS), x.dtype).at[:, :d].set(x)
    return padded.reshape(n_rows, nb, _fa.BLOCK_ROWS, _fa.BLOCK_LANES), d


# The tiles per grid step of each compress kernel a program calls, noted
# while :func:`compress_tiles_traced` collects.
_traced_tiles: list[int] | None = None


@contextlib.contextmanager
def compress_tiles_traced() -> Iterator[list[int]]:
    """Collect the tiles per grid step of every compress kernel that a
    program tracing inside the block calls, in call order.  A program
    traces once: a later call of it notes nothing, so the caller keeps
    what the tracing call collected."""
    global _traced_tiles
    outer, _traced_tiles = _traced_tiles, []
    try:
        yield _traced_tiles
    finally:
        _traced_tiles = outer


def _note_tiles(tiles: int) -> int:
    if _traced_tiles is not None:
        _traced_tiles.append(tiles)
    return tiles


def _static_scalar(x, name: str) -> float:
    """Concretise a kernel-bound scalar for the Pallas branch.

    The Pallas kernels bake these into the kernel body, so a traced value
    (a config-axis sweep) cannot reach them — the sweep driver must demote
    the knob to a per-shape-class constant first (it does, on TPU).
    """
    try:
        return float(x)
    except (jax.errors.ConcretizationTypeError, TypeError) as e:
        raise ValueError(
            f"{name} must be a concrete Python number on the Pallas kernel "
            f"path (it is baked into the kernel body); traced values are "
            f"only supported with use_pallas=False"
        ) from e


def _block_k(k_frac) -> jax.Array | int:
    """Per-block keep count from a keep fraction; traced fractions give a
    traced count (used only in bisection comparisons on the oracle path)."""
    if isinstance(k_frac, (int, float)):
        return max(1, int(round(k_frac * BLOCK_ELEMS)))
    return jnp.maximum(
        1.0, jnp.round(jnp.asarray(k_frac, jnp.float32) * BLOCK_ELEMS)
    )


@functools.partial(
    jax.jit, static_argnames=("n_fog", "k", "quantize", "interpret", "tiles")
)
def _compress_aggregate_pallas(
    deltas, err, fog_id, weights, n_fog: int, k: int, quantize: bool,
    interpret: bool, tiles: int,
):
    blocks, d = _pad_blocks_batch(deltas)
    err_blocks, _ = _pad_blocks_batch(err)
    fog_blocks, new_err = _fa.compress_aggregate_blocks(
        blocks, err_blocks, fog_id, weights, n_fog, k, quantize, interpret,
        tiles,
    )
    fog_sum = fog_blocks.reshape(n_fog, -1)[:, :d]
    return fog_sum, new_err.reshape(deltas.shape[0], -1)[:, :d]


@functools.partial(jax.jit, static_argnames=("n_fog", "quantize"))
def _compress_aggregate_ref(
    deltas, err, fog_id, weights, k, n_fog: int, quantize: bool
):
    blocks, d = _pad_blocks_batch(deltas)
    err_blocks, _ = _pad_blocks_batch(err)
    n_rows = blocks.shape[0]
    fog_blocks, new_err = _ref.compress_aggregate_ref(
        blocks.reshape(n_rows, blocks.shape[1], -1),
        err_blocks.reshape(n_rows, blocks.shape[1], -1),
        fog_id,
        weights,
        n_fog,
        k,
        quantize,
    )
    fog_sum = fog_blocks.reshape(n_fog, -1)[:, :d]
    return fog_sum, new_err.reshape(deltas.shape[0], -1)[:, :d]


def compress_aggregate(
    deltas: jax.Array,    # (N, d) raw per-client flat updates
    err: jax.Array,       # (N, d) error-feedback buffers
    fog_id: jax.Array,    # (N,) int32 cluster assignment
    weights: jax.Array,   # (N,) f32, zeroed for non-participants
    n_fog: int,
    k_frac: float | jax.Array,
    quantize: bool = True,
    use_pallas: bool = False,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused EF Top-K (+ int8) compression and weighted fog accumulation.

    One pass over the (N, d) updates: each client's blockwise
    reconstruction is accumulated directly into its fog cluster's buffer
    instead of being materialised densely and re-read by a segment-sum.

    Returns (fog_sum (n_fog, d) f32 — UNNORMALISED weighted sums
    ``sum_{i in C_m} w_i recon_i``; divide by the per-fog weight totals for
    Eq. 13 — and new_err (N, d)).  ``k_frac`` may be traced on the oracle
    path — the selection is a bisection against the keep count, so swept
    compression ratios batch into one program.
    """
    if use_pallas:
        k = max(1, int(round(_static_scalar(k_frac, "k_frac") * BLOCK_ELEMS)))
        tiles = _note_tiles(_fa.dense_tiles_per_step(deltas.shape[0], n_fog))
        return _compress_aggregate_pallas(
            deltas, err, fog_id, weights, n_fog, k, quantize, interpret,
            tiles=tiles,
        )
    return _compress_aggregate_ref(
        deltas, err, fog_id, weights, _block_k(k_frac), n_fog, quantize
    )


def wire_k(k_frac) -> int:
    """Concrete per-block slot count for the sparse wire format.

    The wire is shape-bearing (k indices + k codes per block), so unlike
    the bisection keep-count it can NEVER be traced: a swept ``rho_s``
    stays on the dense oracle, a concrete one gets the sparse wire.
    """
    k = max(1, int(round(_static_scalar(k_frac, "k_frac") * BLOCK_ELEMS)))
    return min(k, BLOCK_ELEMS)


@functools.partial(
    jax.jit, static_argnames=("k", "quantize", "interpret", "tiles")
)
def _compress_wire_pallas(deltas, err, k: int, quantize: bool,
                          interpret: bool, tiles: int):
    blocks, d = _pad_blocks_batch(deltas)
    err_blocks, _ = _pad_blocks_batch(err)
    idx, q, scale, new_err = _fa.compress_wire_blocks(
        blocks, err_blocks, k, quantize, interpret, tiles
    )
    return (idx[:, :, 0, :k], q[:, :, 0, :k], scale[:, :, 0, 0],
            new_err.reshape(deltas.shape[0], -1)[:, :d])


@functools.partial(jax.jit, static_argnames=("k", "quantize"))
def _compress_wire_ref(deltas, err, k: int, quantize: bool):
    blocks, d = _pad_blocks_batch(deltas)
    err_blocks, _ = _pad_blocks_batch(err)
    n_rows, nb = blocks.shape[:2]
    idx, q, scale, new_err = _ref.compress_wire_ref(
        blocks.reshape(n_rows, nb, -1),
        err_blocks.reshape(n_rows, nb, -1),
        k,
        quantize,
    )
    return idx, q.astype(jnp.float32), scale, (
        new_err.reshape(n_rows, -1)[:, :d]
    )


def compress_wire(
    deltas: jax.Array,    # (N, d) raw per-client flat updates
    err: jax.Array,       # (N, d) error-feedback buffers
    k_frac: float,
    quantize: bool = True,
    use_pallas: bool = False,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Emit the sparse wire format for a batch of clients.

    Returns (idx (N, nb, k) int32, q (N, nb, k) f32 int8-valued codes,
    scale (N, nb) f32, new_err (N, d)).  Per block the wire is k indices +
    k int8 codes + one f32 scale — the Eq. 31 payload as a real in-memory
    object, ~``rho_s * d`` of the dense row.  ``k_frac`` must be concrete
    (the wire is shape-bearing).
    """
    k = wire_k(k_frac)
    if use_pallas:
        n, d = deltas.shape
        nb = max(1, -(-d // BLOCK_ELEMS))
        tiles = _note_tiles(_fa.wire_tiles_per_step(n, nb, _fa.slot_pad(k)))
        return _compress_wire_pallas(deltas, err, k, quantize, interpret,
                                     tiles)
    return _compress_wire_ref(deltas, err, k, quantize)


@functools.partial(jax.jit, static_argnames=("n_fog", "d", "interpret"))
def _wire_aggregate_pallas(idx, q, scale, fog_id, weights, n_fog: int,
                           d: int, interpret: bool):
    n, nb, k = idx.shape
    # Kernel layout: slots padded to whole lanes with no-op (index 0,
    # code 0) slots, the per-block scale broadcast along one lane row.
    pad = ((0, 0), (0, 0), (0, _fa.slot_pad(k) - k))
    fog_blocks = _fa.wire_aggregate_blocks(
        jnp.pad(idx, pad)[:, :, None, :],
        jnp.pad(q.astype(jnp.float32), pad)[:, :, None, :],
        jnp.broadcast_to(scale[:, :, None, None], (n, nb, 1, _fa.BLOCK_LANES)),
        fog_id, weights, n_fog, interpret,
    )
    return fog_blocks.reshape(n_fog, -1)[:, :d]


@functools.partial(jax.jit, static_argnames=("n_fog", "d"))
def _wire_aggregate_ref(idx, q, scale, fog_id, weights, n_fog: int, d: int):
    fog_blocks = _ref.wire_aggregate_ref(
        idx, q, scale, fog_id, weights, n_fog, BLOCK_ELEMS
    )
    return fog_blocks.reshape(n_fog, -1)[:, :d]


def wire_aggregate(
    idx: jax.Array,       # (N, nb, k) int32 wire indices
    q: jax.Array,         # (N, nb, k) codes
    scale: jax.Array,     # (N, nb) f32 per-block scales
    fog_id: jax.Array,    # (N,) int32 cluster assignment
    weights: jax.Array,   # (N,) f32, zeroed for non-participants
    n_fog: int,
    d: int,
    use_pallas: bool = False,
    interpret: bool = True,
) -> jax.Array:
    """Weighted scatter-accumulate of wire payloads into fog buffers.

    Returns fog_sum (n_fog, d) f32 (unnormalised weighted sums).  The dense
    (N, d) reconstructions never exist — contributions go straight from the
    k-slot wire into the accumulators, so the transient footprint is the
    wire plus O(n_fog * d), independent of N.
    """
    if use_pallas:
        return _wire_aggregate_pallas(
            idx, q, scale, fog_id, weights, n_fog, d, interpret
        )
    return _wire_aggregate_ref(idx, q, scale, fog_id, weights, n_fog, d)


def compress_aggregate_wire(
    deltas: jax.Array,    # (N, d) raw per-client flat updates
    err: jax.Array,       # (N, d) error-feedback buffers
    fog_id: jax.Array,    # (N,) int32 cluster assignment
    weights: jax.Array,   # (N,) f32, zeroed for non-participants
    n_fog: int,
    k_frac: float,
    quantize: bool = True,
    use_pallas: bool = False,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Sparse-wire twin of :func:`compress_aggregate`: emit the wire, then
    scatter-accumulate it, without a dense per-client reconstruction on
    either path.  Same contract — (fog_sum (n_fog, d) unnormalised,
    new_err (N, d)) — equal to the dense path up to f32 summation order.
    ``k_frac`` must be concrete (shape-bearing); traced sweeps keep the
    dense oracle.
    """
    idx, q, scale, new_err = compress_wire(
        deltas, err, k_frac, quantize, use_pallas, interpret
    )
    fog_sum = wire_aggregate(
        idx, q, scale, fog_id, weights, n_fog, deltas.shape[1],
        use_pallas, interpret,
    )
    return fog_sum, new_err


def _fog_weight_totals(fog_id, weights, n_fog: int) -> jax.Array:
    return jnp.sum(
        jnp.where(
            fog_id[None, :] == jnp.arange(n_fog)[:, None],
            weights[None, :].astype(jnp.float32), 0.0,
        ),
        axis=1,
    )


@functools.partial(jax.jit, static_argnames=("n_fog", "mode"))
def _robust_aggregate_ref(recon, fog_id, weights, trim_frac, n_fog, mode):
    return _ref.robust_aggregate_ref(
        recon, fog_id, weights, n_fog, trim_frac, mode
    )


@functools.partial(
    jax.jit, static_argnames=("n_fog", "beta", "mode", "interpret")
)
def _robust_aggregate_pallas(
    recon, fog_id, weights, n_fog: int, beta: float, mode: str,
    interpret: bool,
):
    blocks, d = _pad_blocks_batch(recon)
    out = _ra.robust_aggregate_blocks(
        blocks, fog_id, weights, n_fog, beta, mode, interpret
    )
    return (
        out.reshape(n_fog, -1)[:, :d],
        _fog_weight_totals(fog_id, weights, n_fog),
    )


def robust_aggregate(
    recon: jax.Array,     # (N, d) per-client dequantised reconstructions
    fog_id: jax.Array,    # (N,) int32 cluster assignment
    weights: jax.Array,   # (N,) f32, zeroed for non-participants
    n_fog: int,
    trim_frac: float | jax.Array,
    mode: str = "trimmed",
    use_pallas: bool = False,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Coordinate-wise Byzantine-robust fog aggregation (weighted trimmed
    mean / weighted median) as an alternative to the weighted-sum reduce.

    Returns (fog_out (n_fog, d) f32 — the NORMALISED robust aggregate per
    fog, zeros for empty fogs — and fog_weight (n_fog,), the Eq. 16
    gateway weights).  At ``trim_frac == 0`` this reproduces
    ``fog_sum / max(fog_weight, eps)`` exactly (the equivalence pin).
    ``trim_frac`` may be traced on the oracle path; the Pallas kernel bakes
    it into the kernel body and needs a concrete number.
    """
    if mode not in ("trimmed", "median"):
        raise ValueError(
            f"robust mode must be 'trimmed' or 'median', got {mode!r}"
        )
    if use_pallas:
        beta = min(max(_static_scalar(trim_frac, "trim_frac"), 0.0), 0.4995)
        return _robust_aggregate_pallas(
            recon, fog_id, weights, n_fog, beta, mode, interpret
        )
    return _robust_aggregate_ref(
        recon, fog_id, weights, trim_frac, n_fog, mode
    )


def _pad2(a: jax.Array, rows: int, cols: int) -> jax.Array:
    """Zero-pad a 2-D array up to (rows, cols)."""
    return jnp.zeros((rows, cols), a.dtype).at[: a.shape[0], : a.shape[1]].set(a)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def fused_score(
    x: jax.Array,        # (R, d) telemetry rows
    params: Any,         # autoencoder params: list of {"w", "b"} layers
    tau: jax.Array,      # scalar or (R,) per-row thresholds
    use_pallas: bool = False,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused anomaly scoring: AE forward + squared-L2 reconstruction error
    + threshold compare in one pass over the rows (serving hot path).

    Layout owner for :mod:`repro.kernels.fused_score`: rows are zero-padded
    to whole SCORE_ROWS tiles and every layer dimension to a LANES
    multiple (padded-row thresholds are +inf so their flags stay False).
    Returns (err (R,) f32, flags (R,) bool); the dense reconstruction is
    never materialised in HBM on the kernel path.
    """
    r = x.shape[0]
    ws = tuple(layer["w"] for layer in params)
    bs = tuple(layer["b"] for layer in params)
    tau_rows = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (r,))
    if not use_pallas:
        return _ref.fused_score_ref(x, ws, bs, tau_rows)
    return _score_pallas(
        _fs.score_blocks, x, tau_rows,
        tuple(w.astype(jnp.float32) for w in ws), None, bs, interpret,
    )


def _score_pallas(kernel, x, tau_rows, ws, sws, bs, interpret):
    """Lay out one score kernel's call: rows zero-padded to whole
    SCORE_ROWS tiles (padded-row thresholds +inf, so their flags stay
    False) and every layer dimension to a LANES multiple; ``sws`` are the
    int8 kernel's per-channel scales (None for f32 weights)."""
    r, d = x.shape
    rows_pad = max(1, -(-r // _fs.SCORE_ROWS)) * _fs.SCORE_ROWS
    dims = (d,) + tuple(w.shape[1] for w in ws)     # layer output dims
    dims_pad = tuple(max(1, -(-dd // _fs.LANES)) * _fs.LANES for dd in dims)
    x_pad = _pad2(x.astype(jnp.float32), rows_pad, dims_pad[0])
    layers = [tuple(
        _pad2(w, dims_pad[i], dims_pad[i + 1]) for i, w in enumerate(ws)
    )]
    if sws is not None:
        layers.append(tuple(
            _pad2(s.astype(jnp.float32).reshape(1, -1), 1, dims_pad[i + 1])
            for i, s in enumerate(sws)
        ))
    layers.append(tuple(
        _pad2(b.astype(jnp.float32)[None, :], 1, dims_pad[i + 1])
        for i, b in enumerate(bs)
    ))
    tau_pad = jnp.full((rows_pad,), jnp.inf, jnp.float32).at[:r].set(tau_rows)
    err, flag = kernel(
        x_pad, tau_pad.reshape(-1, 1, _fs.SCORE_ROWS), *layers, interpret
    )
    return err.reshape(-1)[:r], flag.reshape(-1)[:r] > 0.0


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def fused_score_q8(
    x: jax.Array,        # (R, d) telemetry rows
    qparams: Any,        # quantized AE params: list of {"qw", "sw", "b"}
    tau: jax.Array,      # scalar or (R,) per-row thresholds
    use_pallas: bool = False,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """int8-serving-weight sibling of :func:`fused_score`.

    ``qparams`` holds per-layer int8 weights with per-output-channel f32
    scales (``serving/score.quantize_params``); dequantisation happens
    inside the fused program (jnp oracle and Pallas kernel alike), so the
    resident weight buffers stay int8.  Same padding contract as
    :func:`fused_score` — int8 zero padding dequantises to exact zeros.
    """
    r = x.shape[0]
    qws = tuple(layer["qw"] for layer in qparams)
    sws = tuple(layer["sw"] for layer in qparams)
    bs = tuple(layer["b"] for layer in qparams)
    tau_rows = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (r,))
    if not use_pallas:
        return _ref.fused_score_q8_ref(x, qws, sws, bs, tau_rows)
    return _score_pallas(_fs.score_blocks_q8, x, tau_rows, qws, sws, bs, interpret)


def _ravel_deltas(dws, dbs, n):
    # ravel_pytree order for a list of {"b", "w"} dicts: per layer, bias
    # first (dict keys sort alphabetically), then the row-major weight.
    return jnp.concatenate(
        [part for dw, db in zip(dws, dbs)
         for part in (db.reshape(n, -1), dw.reshape(n, -1))],
        axis=1,
    )


@functools.partial(jax.jit, static_argnames=("use_prox",))
def _local_train_ref(params, data, idx, lr, prox_mu, use_prox: bool):
    ws = tuple(layer["w"] for layer in params)
    bs = tuple(layer["b"] for layer in params)
    n = data.shape[0]
    new_ws, new_bs, losses = jax.vmap(
        lambda xx, ii: _ref.local_train_ref(
            xx, ii, ws, bs, lr, prox_mu, use_prox=use_prox
        )
    )(data, idx)
    dws = [nw - w[None] for nw, w in zip(new_ws, ws)]
    dbs = [nb.reshape(n, 1, -1) - b[None, None] for nb, b in
           zip(new_bs, bs)]
    return _ravel_deltas(dws, dbs, n), losses


def local_train_pack(dims: tuple[int, ...]) -> int:
    """Clients the local-train kernel packs side by side into each 128-lane
    tile, from the autoencoder's layer widths ``(D, *hidden, D)``: the
    paper AE (widths <= 32) packs 4, a D = 38 detector 3, D = 55 two, any
    width over 64 one."""
    return max(1, _flt.LANES // max(dims))


def _round_up(n: int, unit: int) -> int:
    return max(1, -(-n // unit)) * unit


def _side_by_side(a: jax.Array, pack: int, width: int) -> jax.Array:
    """(N_packs * pack, rows, d) -> (N_packs, rows, pack * width): client c
    of each pack at lanes [c * width, c * width + d), zeros between."""
    m, rows, d = a.shape
    if pack == 1:
        return a
    a = jnp.pad(a, ((0, 0), (0, 0), (0, width - d)))
    return (a.reshape(m // pack, pack, rows, width).transpose(0, 2, 1, 3)
            .reshape(m // pack, rows, pack * width))


def _diag_copies(a, pack, width, rows, cols, row_step):
    """``pack`` copies of 2-D ``a`` at (c * row_step, c * width), zeros
    elsewhere, in a (rows, cols) f32 tile."""
    out = jnp.zeros((rows, cols), jnp.float32)
    for c in range(pack):
        r, l = c * row_step, c * width
        out = out.at[r:r + a.shape[0], l:l + a.shape[1]].set(a.astype(jnp.float32))
    return out


def _diag_blocks(a, pack, width, shape, row_step, n):
    """Inverse of :func:`_diag_copies` over packs: (N_packs, R, C) ->
    (n, *shape), client c of each pack from (c * row_step, c * width)."""
    rows, cols = shape
    parts = [a[:, c * row_step:c * row_step + rows, c * width:c * width + cols]
             for c in range(pack)]
    return jnp.stack(parts, axis=1).reshape(-1, rows, cols)[:n]


@functools.partial(
    jax.jit, static_argnames=("lr", "prox_mu", "interpret")
)
def _local_train_pallas(
    params, data, idx, lr: float, prox_mu: float, interpret: bool
):
    ws = tuple(layer["w"] for layer in params)
    bs = tuple(layer["b"] for layer in params)
    n, window, d = data.shape
    steps, bsz = idx.shape[1], idx.shape[2]
    lanes, sub = _flt.LANES, _flt.SUBLANES
    dims = (d,) + tuple(w.shape[1] for w in ws)
    pack, width = local_train_pack(dims), max(dims)
    n_all = _round_up(n, pack)          # pad clients fill the last pack
    # Client c of a pack owns lanes [c * width, c * width + d_l) of layer l.
    dims_pad = tuple(_round_up((pack - 1) * width + dd, lanes) for dd in dims)
    w_pad = _round_up(window, lanes)
    b_pad = _round_up(bsz, sub)
    s_pad = _round_up(steps, sub)
    x = jnp.zeros((n_all, window, d), jnp.float32).at[:n].set(
        data.astype(jnp.float32))
    x = _side_by_side(x, pack, width)
    x_pad = jnp.pad(x, ((0, 0), (0, w_pad - window), (0, dims_pad[0] - x.shape[2])))
    # The P index tables side by side: row s of a pack is its clients' step s.
    idx_pad = _side_by_side(
        jnp.full((n_all, s_pad, b_pad), -1, jnp.int32)
        .at[:n, :steps, :bsz].set(idx.astype(jnp.int32)),
        pack, b_pad,
    )
    ws_pad = tuple(
        _diag_copies(w, pack, width, dims_pad[i], dims_pad[i + 1], width)
        for i, w in enumerate(ws)
    )
    bs_pad = tuple(
        _diag_copies(b[None, :], pack, width, 1, dims_pad[i + 1], 0)
        for i, b in enumerate(bs)
    )
    dws_p, dbs_p, loss = _flt.local_train_blocks(
        x_pad, idx_pad, ws_pad, bs_pad, steps, bsz, lr, prox_mu,
        interpret, pack=pack, width=width,
    )
    dws = [_diag_blocks(dw, pack, width, w.shape, width, n)
           for dw, w in zip(dws_p, ws)]
    dbs = [_diag_blocks(db, pack, width, (1, b.shape[0]), 0, n)
           for db, b in zip(dbs_p, bs)]
    return _ravel_deltas(dws, dbs, n), loss[:, 0, :pack].reshape(-1)[:n]


def local_train(
    params: Any,          # autoencoder params: list of {"w", "b"} layers
    data: jax.Array,      # (N, window, D) per-client resident windows
    idx: jax.Array,       # (N, steps, bsz) int32 minibatch row indices
    lr: float,
    prox_mu: float = 0.0,
    use_pallas: bool = False,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused E-epoch local training for a batch of clients (the client
    phase of a federated round in ONE operator).

    Layout owner for :mod:`repro.kernels.fused_local_train`: it packs
    :func:`local_train_pack` clients side by side into each 128-lane tile
    (windows and biases in lane blocks, weights as diagonal copies),
    zero-pads windows and every layer dimension to LANES multiples and the
    (steps, bsz) index tables to SUBLANES multiples on both axes, -1-filled
    so padded rows select nothing, then cuts each client's diagonal block
    back out.  ``idx`` comes from
    :func:`repro.data.pipeline.multi_epoch_indices`, which makes this
    batch-for-batch identical to ``local_sgd`` over
    ``multi_epoch_batches`` — without the dense (steps, bsz, D) stream.

    ``lr`` / ``prox_mu`` may be traced on the oracle path (config-axis
    sweeps); the Pallas kernel bakes them into the kernel body and needs
    concrete numbers.

    Returns (flat_deltas (N, d) f32 in ``ravel_pytree`` leaf order, i.e.
    exactly ``ravel_pytree(theta_i^E - theta^t)``, and mean_losses (N,)).
    The deltas chain straight into :func:`compress_aggregate`.
    """
    if use_pallas:
        return _local_train_pallas(
            params, data, idx, _static_scalar(lr, "lr"),
            _static_scalar(prox_mu, "prox_mu"), interpret,
        )
    use_prox = not (isinstance(prox_mu, (int, float)) and prox_mu == 0.0)
    return _local_train_ref(params, data, idx, lr, prox_mu, use_prox)


def swa_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    window: int,
    use_pallas: bool = False,
    interpret: bool = True,
) -> jax.Array:
    """Single-token sliding-window GQA attention (see swa_attention.py)."""
    if use_pallas:
        return _swa.swa_decode_attention(
            q, k_cache, v_cache, cache_len, window, interpret
        )
    return _ref.sliding_window_decode_attention_ref(
        q, k_cache, v_cache, cache_len, window
    )
