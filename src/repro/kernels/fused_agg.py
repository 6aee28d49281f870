"""Pallas TPU kernel: fused compress-and-aggregate (EF Top-K + int8 +
weighted fog accumulation) — the federated round's hot path in ONE pass.

Compressing each client and then segment-summing would make three HBM
round-trips per round: a dense reconstruction per client, the error
buffer, and a re-read of every reconstruction.  This kernel loads each
(client, block) tile once, runs the identical sparsify-quantise-residual
computation in VMEM (bit-for-bit the :func:`repro.kernels.ref.compress_ref`
semantics), and accumulates ``w_i * recon_i`` straight into a per-fog VMEM
accumulator — the dense (N, d) reconstruction never exists in HBM, only the
(n_fog, d) weighted sums and the (N, d) error buffer (which is round state
and has to be written regardless).

Grid layout: ``(nb, N / T)`` with the client axis INNERMOST, so the fog
accumulator block for column ``j`` stays resident in VMEM across all
sequential client steps (zeroed at ``i == 0``, flushed when ``j``
advances).  Each step takes T clients and bisects their Top-K thresholds
together (:func:`_select_and_quantize`): the bisection is a serial chain
of reduce-to-scalar steps, so one tile a step leaves the chip waiting on
that chain, while T tiles share it.  ``fog_id`` / ``weights`` ride in as
scalar-prefetch operands (SMEM), which is what lets the kernel scatter
into a dynamic fog row — no sorting of clients by cluster required.  The
per-fog block is (n_fog, BLOCK_ROWS, BLOCK_LANES) f32: at the paper's M =
N/10 (n_fog <= 20) that is ~640 KiB, comfortably inside VMEM next to the
client tiles.  The wire emitter steps over its (client, block) tiles T at
a time the same way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import BISECT_ITERS

# The kernels' unit of work: a flat update vector is zero-padded and
# reshaped (by ops.py) into (BLOCK_ROWS, BLOCK_LANES) tiles.  BLOCK_LANES =
# 128 matches the VPU lane count; 64 rows make a tile 8,192 f32 = 32 KiB.
BLOCK_ROWS = 64
BLOCK_LANES = 128
BLOCK_ELEMS = BLOCK_ROWS * BLOCK_LANES

# Mosaic's default scoped-VMEM limit on v5e.  Kernels whose resident blocks
# outgrow it (the identity-segment fog accumulator of the robust path holds
# N_chunk x 32 KiB) raise the limit explicitly via :func:`vmem_params`.
DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024
_VMEM_HEADROOM = 4 * 1024 * 1024
TILE_BYTES = BLOCK_ROWS * BLOCK_LANES * 4
# Tiles per grid step of the compress kernels: each step bisects up to
# MAX_TILES_PER_STEP tiles together, within a VMEM budget of half a v5e's
# 128 MiB.  A tile in a step costs its double-buffered input and output
# tiles and the body's slab temporaries (about 11 tiles' worth by the
# compiler's scoped allocation on a v5e, rounded up to 14).
MAX_TILES_PER_STEP = 16
VMEM_BUDGET = 64 * 1024 * 1024
STEP_TILE_BYTES = 14 * TILE_BYTES
_EXACT = jax.lax.Precision.HIGHEST   # one-hot dots must move f32 values exactly


def vmem_params(need_bytes: int) -> pltpu.CompilerParams | None:
    """Compiler params raising the scoped-VMEM limit to ``need_bytes`` plus
    headroom when the default would not hold the kernel's blocks."""
    if need_bytes + _VMEM_HEADROOM <= DEFAULT_SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need_bytes + _VMEM_HEADROOM)


def _per_tile(reduce, x):
    """(T, R, L) -> (T, 1, 1): rows first (mostly vector adds), then the
    lanes of one row per tile.  Mosaic lowers one axis at a time."""
    return reduce(reduce(x, axis=1, keepdims=True), axis=2, keepdims=True)


def _select_and_quantize(v, k: int, quantize: bool):
    """EF Top-K selection + int8 round trip of a (T, R, L) stack of tiles,
    each tile exactly by the :func:`repro.kernels.ref.compress_aggregate_ref`
    rules.

    The T bisections run together: the carries are (T, 1, 1), and each of
    the ``BISECT_ITERS`` steps compares the whole slab once and ends in T
    per-tile counts, so the tiles share one serial chain of reductions
    instead of T.  Every tile keeps its own max, midpoints and ``count >
    k`` rule, so it gets the threshold it would get alone.

    Returns (recon tiles, scale (T, 1, 1), threshold (T, 1, 1)): a
    coordinate survives where its magnitude exceeds the threshold, and
    ``scale`` is the block max / 127 (1.0 without quantisation): whenever
    anything survives, the block max survives too, so it equals
    max|sparse|.
    """
    absv = jnp.abs(v)
    amax = _per_tile(jnp.max, absv)

    # Threshold bisection, identical to ref.bisect_threshold: invariant
    # count(> hi) <= k <= count(> lo).
    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        take = _per_tile(jnp.sum, (absv > mid).astype(jnp.int32)) > k
        return jnp.where(take, mid, lo), jnp.where(take, hi, mid)

    _, hi = jax.lax.fori_loop(
        0, BISECT_ITERS, body, (jnp.full_like(amax, -1.0), amax)
    )
    survive = absv > hi
    if quantize:
        scale = amax / 127.0
        safe = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(v / safe), -127.0, 127.0)
        recon = jnp.where(survive & (scale > 0), q * scale, 0.0)
    else:
        scale = jnp.ones_like(amax)
        recon = jnp.where(survive, v, 0.0)
    return recon, scale, hi


def _fused_agg_kernel(
    fog_id_ref,   # (N,) int32  scalar prefetch
    w_ref,        # (N,) f32    scalar prefetch
    delta_ref,    # (T, 1, R, L)
    err_ref,      # (T, 1, R, L)
    fog_ref,      # (n_fog, 1, R, L) accumulator, resident across clients
    new_err_ref,  # (T, 1, R, L)
    *,
    k: int,
    quantize: bool,
):
    i = pl.program_id(1)  # client step (innermost grid axis)
    tiles = delta_ref.shape[0]

    @pl.when(i == 0)
    def _():
        fog_ref[...] = jnp.zeros_like(fog_ref)

    v = delta_ref[:, 0] + err_ref[:, 0]                  # (T, R, L)
    recon, _, _ = _select_and_quantize(v, k, quantize)
    new_err_ref[:, 0] = v - recon
    # Scatter-accumulate each client into its fog row (data-dependent
    # index from the prefetched cluster assignment), in client order.
    for t in range(tiles):
        c = i * tiles + t
        f = fog_id_ref[c]
        fog_ref[f, 0] = fog_ref[f, 0] + w_ref[c] * recon[t]


def _compact(survive, v, scale, kp: int, quantize: bool):
    """Pack one tile's survivors into ``kp`` slots in ascending coordinate
    order (slots past the survivor count carry index 0 and code 0).

    The packing is a stream compaction built from masks and one-hot
    matmuls, all in the lane-major ``(., KP)`` slot layout so nothing is
    transposed: a triangular matmul gives each survivor its rank, each
    slot finds the tile row holding its rank, a one-hot matmul gathers
    that row into the slot's column, and a lane match inside the row picks
    the coordinate.  Returns (idx (1, KP) int32, codes (1, KP) f32).
    """
    rows, lanes = v.shape
    sf = survive.astype(jnp.float32)
    # rank_in_row[r, l] = survivors left of l in row r (0/1 sums: exact).
    upper = (
        jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes), 0)
        < jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes), 1)
    ).astype(jnp.float32)
    rank_in_row = jnp.dot(sf, upper, preferred_element_type=jnp.float32)
    # row_off[r] = survivors in rows above r; row_end[r] = through row r.
    lower = (
        jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
        < jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    ).astype(jnp.float32)
    row_off = jnp.sum(
        jnp.dot(lower, sf, preferred_element_type=jnp.float32),
        axis=1, keepdims=True,
    )                                                    # (R, 1)
    row_end = row_off + jnp.sum(sf, axis=1, keepdims=True)
    n_surv = jnp.sum(sf)

    slot = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1).astype(jnp.float32)
    valid = slot < n_surv                                # (1, KP)
    # Row holding slot s = number of rows that end at or before s.
    slot_row = jnp.sum(
        (row_end <= slot).astype(jnp.int32), axis=0, keepdims=True
    )                                                    # (1, KP)
    row_hot = (
        jax.lax.broadcasted_iota(jnp.int32, (rows, kp), 0) == slot_row
    ).astype(jnp.float32)                                # (R, KP)
    slot_off = jnp.sum(row_hot * row_off, axis=0, keepdims=True)
    # Gather each slot's row into its column: (L, KP) = row^T @ row_hot.
    tn = (((0,), (0,)), ((), ()))
    key = jnp.where(survive, rank_in_row, -1.0)
    row_key = jax.lax.dot_general(key, row_hot, tn, precision=_EXACT,
                                  preferred_element_type=jnp.float32)
    row_val = jax.lax.dot_general(v, row_hot, tn, precision=_EXACT,
                                  preferred_element_type=jnp.float32)
    hit = (row_key == slot - slot_off) & valid           # (L, KP)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (lanes, kp), 0)
    slot_lane = jnp.sum(jnp.where(hit, lane_iota, 0), axis=0, keepdims=True)
    vals = jnp.sum(jnp.where(hit, row_val, 0.0), axis=0, keepdims=True)
    idx = jnp.where(valid, slot_row * lanes + slot_lane, 0)
    if quantize:
        safe = jnp.where(scale > 0, scale, 1.0)
        vals = jnp.clip(jnp.round(vals / safe), -127.0, 127.0)
    return idx, vals


def _wire_emit_kernel(
    delta_ref,    # (T, R, L)
    err_ref,      # (T, R, L)
    idx_ref,      # (T, 1, KP) int32 slots
    q_ref,        # (T, 1, KP) f32 codes (int8-valued when quantizing)
    scale_ref,    # (T, 1, L) f32, the block scale broadcast along lanes
    new_err_ref,  # (T, R, L)
    thr_ref,      # (T, 1, L) f32 scratch: each tile's threshold
    *,
    k: int,
    quantize: bool,
):
    """Emit the sparse wire for T (client, block) tiles.

    Identical selection to :func:`_fused_agg_kernel`, but the survivors are
    packed into slots (:func:`_compact`) instead of a dense masked tile —
    the rho_s-sized object the acoustic link carries.  The compaction runs
    one tile at a time in a loop, which keeps its (L, KP) operands in VMEM
    once rather than T times.  Codes are f32 holding exact int8 values.
    """
    tiles, lanes = delta_ref.shape[0], delta_ref.shape[2]
    kp = idx_ref.shape[2]
    v = delta_ref[...] + err_ref[...]                    # (T, R, L)
    recon, scale, hi = _select_and_quantize(v, k, quantize)
    new_err_ref[...] = v - recon
    scale_ref[...] = jnp.broadcast_to(scale, (tiles, 1, lanes))
    thr_ref[...] = jnp.broadcast_to(hi, (tiles, 1, lanes))

    def compact(t, carry):
        vt = delta_ref[t] + err_ref[t]
        survive = jnp.abs(vt) > thr_ref[t]
        scale_t = jnp.max(scale_ref[t], axis=1, keepdims=True)
        idx_ref[t], q_ref[t] = _compact(survive, vt, scale_t, kp, quantize)
        return carry

    jax.lax.fori_loop(0, tiles, compact, 0)


def _wire_agg_kernel(
    fog_id_ref,   # (N,) int32  scalar prefetch
    w_ref,        # (N,) f32    scalar prefetch
    idx_ref,      # (1, 1, 1, KP) int32
    q_ref,        # (1, 1, 1, KP) f32 codes
    scale_ref,    # (1, 1, 1, L) f32 block scale broadcast along lanes
    fog_ref,      # (n_fog, 1, R, L) accumulator, resident across clients
):
    """Weighted scatter-accumulate straight off the wire.

    Same grid discipline as :func:`_fused_agg_kernel` — ``(nb, N)`` with
    clients innermost so the fog block stays VMEM-resident — but the input
    per step is the slot wire, not a dense tile.  The scatter is one
    matmul: ``(R, KP)`` row selector carrying the slot values times the
    ``(L, KP)`` lane selector, contracted over slots.  Slot indices are
    distinct (padding slots carry code 0), so every output coordinate
    receives one term and the f32 values pass through exactly.
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        fog_ref[...] = jnp.zeros_like(fog_ref)

    rows, lanes = fog_ref.shape[2], fog_ref.shape[3]
    kp = idx_ref.shape[3]
    idx = idx_ref[0, 0]                                  # (1, KP)
    scale = jnp.max(scale_ref[0, 0])
    # (q * scale) * w: the oracle's association order.
    contrib = q_ref[0, 0] * scale * w_ref[i]
    row_sel = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (rows, kp), 0) == idx // lanes,
        contrib, 0.0,
    )                                                    # (R, KP)
    lane_sel = (
        jax.lax.broadcasted_iota(jnp.int32, (lanes, kp), 0) == idx % lanes
    ).astype(jnp.float32)                                # (L, KP)
    tile = jax.lax.dot_general(
        row_sel, lane_sel, (((1,), (1,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32,
    )
    f = fog_id_ref[i]
    fog_ref[f, 0] = fog_ref[f, 0] + tile


def slot_pad(k: int) -> int:
    """Slot count of the in-kernel wire layout: ``k`` rounded up to whole
    lanes (ops.py slices the real ``k`` slots back out)."""
    return -(-k // BLOCK_LANES) * BLOCK_LANES


def tiles_per_step(n_tiles: int, resident_bytes: int,
                   tile_bytes: int = STEP_TILE_BYTES,
                   most: int = MAX_TILES_PER_STEP) -> int:
    """Tiles each grid step of a kernel takes together.

    At most ``most`` (for the compress kernels
    :data:`MAX_TILES_PER_STEP`), and no more than fit the VMEM budget
    beside ``resident_bytes`` at ``tile_bytes`` a tile.  Among the counts
    from that cap down to half of it, the largest that divides
    ``n_tiles`` is taken, so that no step is ragged; where none divides,
    the cap is taken and the last step is ragged.
    """
    fit = (VMEM_BUDGET - resident_bytes) // tile_bytes
    cap = max(1, min(most, fit, n_tiles))
    for t in range(cap, cap // 2, -1):
        if n_tiles % t == 0:
            return t
    return cap


def _wire_vmem(kp: int) -> tuple[int, int]:
    """(resident, per-tile) VMEM bytes of the wire emitter at ``kp``
    slots: the compaction's (L, KP) f32 operands, held once, and per tile
    its slab plus double-buffered int32 and f32 slot rows."""
    return 4 * BLOCK_LANES * kp * 4, STEP_TILE_BYTES + 4 * kp * 4


def wire_tiles_per_step(n: int, nb: int, kp: int) -> int:
    """Tiles per grid step of :func:`compress_wire_blocks` on ``n``
    clients of ``nb`` blocks at ``kp`` slots a block."""
    return tiles_per_step(n * nb, *_wire_vmem(kp))


def dense_tiles_per_step(n: int, n_fog: int) -> int:
    """Clients per grid step of :func:`compress_aggregate_blocks`: the
    resident fog accumulator is double-buffered."""
    return tiles_per_step(n, 2 * n_fog * TILE_BYTES)


@functools.partial(
    jax.jit,
    static_argnames=("k_per_block", "quantize", "interpret", "tiles"),
)
def compress_wire_blocks(
    delta: jax.Array,     # (N, nb, BLOCK_ROWS, BLOCK_LANES) f32
    err: jax.Array,       # (N, nb, BLOCK_ROWS, BLOCK_LANES) f32
    k_per_block: int,
    quantize: bool = True,
    interpret: bool = True,
    tiles: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Emit the sparse wire for every (client, block) tile.

    Returns (idx (N, nb, 1, KP) int32, q (N, nb, 1, KP) f32 int8-valued
    codes, scale (N, nb, 1, L) f32 lane-broadcast, new_err like
    ``delta``), with ``KP = slot_pad(k)``: every per-tile output block
    spans its array's last two dims, the layout Mosaic accepts.

    The N x nb tiles are independent, so the grid steps over them in
    their row-major order, ``tiles`` at a time (default
    :func:`wire_tiles_per_step`); a ragged last step reads pad tiles
    whose outputs are dropped.  The slot rows stay per tile, so the wire
    is the same at any ``tiles``.
    """
    n, nb = delta.shape[:2]
    assert delta.shape == (n, nb, BLOCK_ROWS, BLOCK_LANES), delta.shape
    k = min(int(k_per_block), BLOCK_ROWS * BLOCK_LANES)
    kp = slot_pad(k)
    t = tiles or wire_tiles_per_step(n, nb, kp)
    resident, per_tile = _wire_vmem(kp)
    flat = (n * nb, BLOCK_ROWS, BLOCK_LANES)
    tile = pl.BlockSpec((t, BLOCK_ROWS, BLOCK_LANES), lambda s: (s, 0, 0))
    slot = pl.BlockSpec((t, 1, kp), lambda s: (s, 0, 0))
    sc = pl.BlockSpec((t, 1, BLOCK_LANES), lambda s: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(pl.cdiv(n * nb, t),),
        in_specs=[tile, tile],
        out_specs=[slot, slot, sc, tile],
        scratch_shapes=[pltpu.VMEM((t, 1, BLOCK_LANES), jnp.float32)],
    )
    # The barrier keeps the caller's (N, d) -> (N, nb, R, L) relayout a
    # copy: merged with the flattening below, XLA emits one reshape
    # instead, which a v5e runs several times slower.
    delta, err = jax.lax.optimization_barrier((delta, err))
    idx, q, scale, new_err = pl.pallas_call(
        functools.partial(_wire_emit_kernel, k=k, quantize=quantize),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n * nb, 1, kp), jnp.int32),
            jax.ShapeDtypeStruct((n * nb, 1, kp), jnp.float32),
            jax.ShapeDtypeStruct((n * nb, 1, BLOCK_LANES), jnp.float32),
            jax.ShapeDtypeStruct(flat, delta.dtype),
        ],
        compiler_params=vmem_params(resident + t * per_tile),
        interpret=interpret,
    )(delta.reshape(flat), err.reshape(flat))
    return (idx.reshape(n, nb, 1, kp), q.reshape(n, nb, 1, kp),
            scale.reshape(n, nb, 1, BLOCK_LANES), new_err.reshape(delta.shape))


@functools.partial(jax.jit, static_argnames=("n_fog", "interpret"))
def wire_aggregate_blocks(
    idx: jax.Array,       # (N, nb, 1, KP) int32
    q: jax.Array,         # (N, nb, 1, KP) f32 codes
    scale: jax.Array,     # (N, nb, 1, L) f32 lane-broadcast scales
    fog_id: jax.Array,    # (N,) int32
    weights: jax.Array,   # (N,) f32
    n_fog: int,
    interpret: bool = True,
) -> jax.Array:
    """Consume the wire into (n_fog, nb, R, L) weighted sums."""
    n, nb, _, kp = idx.shape
    slot = pl.BlockSpec((1, 1, 1, kp), lambda j, i, *_: (i, j, 0, 0))
    sc = pl.BlockSpec((1, 1, 1, BLOCK_LANES), lambda j, i, *_: (i, j, 0, 0))
    fog_spec = pl.BlockSpec((n_fog, 1, BLOCK_ROWS, BLOCK_LANES),
                            lambda j, i, *_: (0, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, n),
        in_specs=[slot, slot, sc],
        out_specs=[fog_spec],
    )
    (out,) = pl.pallas_call(
        _wire_agg_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_fog, nb, BLOCK_ROWS, BLOCK_LANES),
                                 jnp.float32),
        ],
        compiler_params=vmem_params(2 * n_fog * TILE_BYTES),
        interpret=interpret,
    )(fog_id.astype(jnp.int32), weights.astype(jnp.float32), idx,
      q.astype(jnp.float32), scale)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("n_fog", "k_per_block", "quantize", "interpret", "tiles"),
)
def compress_aggregate_blocks(
    delta: jax.Array,     # (N, nb, BLOCK_ROWS, BLOCK_LANES) f32
    err: jax.Array,       # (N, nb, BLOCK_ROWS, BLOCK_LANES) f32
    fog_id: jax.Array,    # (N,) int32
    weights: jax.Array,   # (N,) f32
    n_fog: int,
    k_per_block: int,
    quantize: bool = True,
    interpret: bool = True,
    tiles: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Run the fused kernel over blocked input.

    Returns (fog_sum (n_fog, nb, R, L) f32 — unnormalised weighted sums —
    and new_err, same shape/dtype as ``delta``).  Each grid step takes
    ``tiles`` clients of one block column (default
    :func:`dense_tiles_per_step`); a ragged last step's pad clients add
    nothing.  The fog accumulator is resident and double-buffered, so the
    robust path's identity segments (``n_fog`` = clients per call) raise
    the scoped-VMEM limit.
    """
    n, nb = delta.shape[:2]
    assert delta.shape == (n, nb, BLOCK_ROWS, BLOCK_LANES), delta.shape
    t = tiles or dense_tiles_per_step(n, n_fog)
    # A ragged last step's pad clients get weight 0.  Whatever a pad tile
    # reads, its reconstruction is finite (NaN and inf never survive the
    # selection, codes are clipped), so each adds an exact zero.
    pad = -n % t
    tile = pl.BlockSpec((t, 1, BLOCK_ROWS, BLOCK_LANES),
                        lambda j, i, *_: (i, j, 0, 0))
    fog_spec = pl.BlockSpec((n_fog, 1, BLOCK_ROWS, BLOCK_LANES),
                            lambda j, i, *_: (0, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, pl.cdiv(n, t)),
        in_specs=[tile, tile],
        out_specs=[fog_spec, tile],
    )
    return pl.pallas_call(
        functools.partial(_fused_agg_kernel, k=k_per_block, quantize=quantize),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_fog, nb, BLOCK_ROWS, BLOCK_LANES),
                                 jnp.float32),
            jax.ShapeDtypeStruct(delta.shape, delta.dtype),
        ],
        compiler_params=vmem_params(
            2 * n_fog * TILE_BYTES + t * STEP_TILE_BYTES
        ),
        interpret=interpret,
    )(jnp.pad(fog_id.astype(jnp.int32), (0, pad)),
      jnp.pad(weights.astype(jnp.float32), (0, pad)), delta, err)
