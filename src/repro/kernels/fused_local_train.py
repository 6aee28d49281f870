"""Pallas TPU kernel: fused local training — a pack of clients' ENTIRE
federated work item (E epochs of minibatch SGD on the paper autoencoder,
Eq. 12) in a single VMEM-resident launch.

The unfused client phase is the last big HBM spender in the round loop:
``data/pipeline.multi_epoch_batches`` gathers a dense ``(E * nb, bs, D)``
batch stream per client per round (``E * nb * bs`` rows re-read from a
``window``-row buffer), and ``optim/sgd.local_sgd`` then scans one
``value_and_grad`` + tree-update per minibatch over it — on the engine's
``(seed, deployment)`` trial grid that is ``O(S * P * N * E * window * D)``
gather traffic before a single useful FLOP.  This kernel instead keeps ONE
copy of each client's ``(window, D)`` window and the broadcast params
resident in VMEM for the whole local phase: each grid step (= K packs of
clients) loads its windows once, then for every minibatch *indexes* the
resident rows (a one-hot selector matmul — the TPU-native gather), runs forward +
manual backward + the SGD/FedProx update fused, and finally writes only the
per-layer parameter DELTAS ``theta_i^E - theta^t`` and the mean loss.  The
dense batch stream never exists anywhere; only the tiny ``(steps, bs)``
int32 permutation table (from ``data/pipeline.multi_epoch_indices``) rides
along, so the client phase chains straight into the fused
compress-and-aggregate kernel and the whole sensor side of a round is two
launches with no dense intermediates.

Layout (ops.py owns it): P = 128 // max(D, hidden widths) clients share
each 128-lane tile side by side (the paper AE, widths <= 32: P = 4; any
width over 64: P = 1, one client per tile).  Client c of a pack owns
lanes [c * Wc, c * Wc + d_l) of every layer, Wc = max(D, hidden widths);
the pack's (window, 128) data tile holds the P windows in their lane
blocks, and every weight tile holds P diagonal copies of theta^t with
exact zeros off the diagonal blocks.  The grid walks ceil(N / P) packs, K
at a time; a pad client (zero window, all -1 indices) trains on zeros in
its own lane block and is sliced off, and so does a pad pack, all of pad
clients, that fills the last grid step.  The window and every layer
dimension are padded to LANES = 128 and each client's (steps, batch)
index table to SUBLANES = 8 on both axes, -1-filled; the P tables sit
side by side, so each SGD step reads the pack's (1, P * B_pad) index row
with one dynamic ref slice.

Per step, the P one-hot selectors gather the P minibatches in one matmul
at HIGHEST precision (exact), each client's rows masked to its lane block;
padded batch rows (a static row range) are masked out of the loss and
gradient.  The forward GEMMs and the backward ``g @ W^T`` stay
block-diagonal because the off-block weights are exactly zero; the weight
gradient ``a^T @ g`` picks up cross-client terms, which a static
block-diagonal select drops before the update, so off-block weights stay
exactly 0 for every step.  Clients therefore never mix while every value
stays finite; a NaN or inf in one client's lanes would reach its
packmates through the GEMMs.  Per-lane sums of the squared error are
summed per lane block at the end: lane c of the loss row holds client c's
mean loss.  At P = 1 the layout and the deltas are those of one client
per tile.  Zero padding is exact end to end: padded window rows are never
selected, padded layer lanes stay identically zero through forward,
backward and the update (tanh(0) = 0, zero weight rows/columns propagate
zeros).  The broadcast params ride as whole-array blocks with the index
map pinned to the origin — resident across all sequential grid steps —
and each pack's working params live in VMEM scratch, re-seeded from the
broadcast blocks at each grid step: at the paper AE four 128x128 f32
anchor matrices, the same again in scratch for each pack, next to the
packs' (window, 128) data tiles.  Every per-step matmul — the one-hot
gather, the four layer GEMMs, and their transposed backward partners —
is MXU-shaped.

One pack's SGD step is a serial chain of about a dozen small matmuls, so
one chain alone leaves the MXU waiting on latency.  The K packs of a grid
step are independent: the loop body runs step s of each of them, K chains
that share no value, and the scheduler overlaps them.  K comes from the
call's shapes (:func:`local_train_packs_per_step`: at most
:data:`MAX_PACKS_PER_STEP`, within a VMEM budget, preferring a divisor of
the pack count); each pack's arithmetic, and so its deltas and loss, is
the same at any K.

FedProx (``mu > 0``) is free here: the anchor ``theta^t`` the proximal
term needs is exactly the resident broadcast block, so the kernel adds
``mu * (theta - anchor)`` to the gradient without any extra traffic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import fused_agg as _fa

LANES = 128      # layer-dimension / window padding unit (VPU lane count)
SUBLANES = 8     # batch-row padding unit (f32 sublane count)
# Packs per grid step: each step interleaves the SGD chains of up to
# MAX_PACKS_PER_STEP packs, within fused_agg's VMEM budget.
MAX_PACKS_PER_STEP = 16


def _in_block(shape, axis: int, c: int, width: int) -> jax.Array:
    """True where the index along ``axis`` lies in client ``c``'s block."""
    i = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return (i >= c * width) & (i < (c + 1) * width)


def _tile_bytes(shape) -> int:
    """VMEM bytes of an f32 / int32 array: its last two dims occupy whole
    (SUBLANES, LANES) tiles."""
    *lead, r, c = shape
    return (math.prod(lead) * -(-r // SUBLANES) * SUBLANES
            * -(-c // LANES) * LANES * 4)


def pack_vmem_bytes(w_pad: int, d_pad: int, s_pad: int, pb_pad: int,
                    w_shapes, b_pad: int) -> tuple[int, int]:
    """(resident, per-pack) VMEM bytes of :func:`local_train_blocks`: the
    double-buffered anchor theta^t once, and per pack its double-buffered
    window, index, delta and loss blocks, its working params and the
    step's gather operands and activations."""
    params = sum(_tile_bytes(w) + _tile_bytes((1, w[1])) for w in w_shapes)
    blocks = (_tile_bytes((w_pad, d_pad)) + _tile_bytes((s_pad, pb_pad))
              + params + _tile_bytes((1, LANES)))
    temps = (_tile_bytes((w_pad, pb_pad)) + _tile_bytes((pb_pad, d_pad))
             + sum(_tile_bytes((b_pad, w[1])) for w in w_shapes))
    return 2 * params, 2 * blocks + params + temps


def local_train_packs_per_step(n_packs: int, per_pack_vmem_bytes: int,
                               resident_bytes: int = 0) -> int:
    """Packs each grid step of the local-train kernel trains together.

    At most :data:`MAX_PACKS_PER_STEP`, and no more than fit the VMEM
    budget beside ``resident_bytes``; among the counts from that cap down
    to half of it, the largest that divides ``n_packs`` (the rule of
    :func:`repro.kernels.fused_agg.tiles_per_step`).
    """
    return _fa.tiles_per_step(n_packs, resident_bytes, per_pack_vmem_bytes,
                              most=MAX_PACKS_PER_STEP)


def _local_train_kernel(
    x_ref,        # (K, W_pad, D_pad) the packs' data windows, side by side
    idx_ref,      # (K, S_pad, P * B_pad) int32 minibatch indices, -1 = padding
    *refs,
    n_layers: int,
    steps: int,
    batch: int,
    lr: float,
    mu: float,
    pack: int,
    width: int,
):
    nl = n_layers
    w_refs = [refs[2 * li] for li in range(nl)]          # anchor theta^t
    b_refs = [refs[2 * li + 1] for li in range(nl)]
    outs = refs[2 * nl:]
    dw_refs = [outs[2 * li] for li in range(nl)]
    db_refs = [outs[2 * li + 1] for li in range(nl)]
    loss_ref = outs[2 * nl]
    scratch = outs[2 * nl + 1:]
    sw = [scratch[2 * li] for li in range(nl)]           # working theta, (K, ...)
    sb = [scratch[2 * li + 1] for li in range(nl)]
    packs = x_ref.shape[0]

    # Re-seed every pack's working params from the resident broadcast
    # blocks: every client starts its local phase from the same theta^t.
    for k in range(packs):
        for li in range(nl):
            sw[li][k] = w_refs[li][...]
            sb[li][k] = b_refs[li][...]

    w_pad, d_pad = x_ref.shape[1:]
    pb_pad = idx_ref.shape[2]
    b_pad = pb_pad // pack
    d_out = w_refs[-1].shape[1]
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (w_pad, pb_pad), 0)
    # 1.0 on real batch rows, 0.0 on padding: every step's index row holds
    # ``batch`` real entries per client, so padding is a static row range.
    row_mask = (
        jax.lax.broadcasted_iota(jnp.int32, (b_pad, d_out), 0) < batch
    ).astype(jnp.float32)
    if pack > 1:
        # Each client's lanes of the gathered rows, and the diagonal blocks
        # of a weight tile (all LANES x LANES when clients are packed).
        x_lanes = [_in_block((b_pad, d_pad), 1, c, width) for c in range(pack)]
        sq = (LANES, LANES)
        diag = _in_block(sq, 0, 0, width) & _in_block(sq, 1, 0, width)
        for c in range(1, pack):
            diag = diag | (_in_block(sq, 0, c, width) & _in_block(sq, 1, c, width))
    inv_b = 1.0 / batch
    tn = (((0,), (0,)), ((), ()))                        # contract dim 0

    def sgd_step(k, s, loss_lanes):
        """SGD step ``s`` of pack ``k``: a chain independent of the
        other packs' chains."""
        idx_row = idx_ref[k, pl.ds(s, 1), :]             # (1, P * B_pad) int32
        # Gather-as-matmul: the transposed one-hot selectors (one column per
        # batch row of each client) pick the P minibatches out of the
        # resident windows in one matmul; padded batch rows select nothing.
        # HIGHEST keeps the gather exact (a default-precision f32 dot may
        # round the data to bf16).
        sel_t = (iota_w == idx_row).astype(jnp.float32)  # (W_pad, P * B_pad)
        xg = jax.lax.dot_general(
            sel_t, x_ref[k], tn, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                                # (P * B_pad, D_pad)
        xb = xg[:b_pad]
        if pack > 1:
            # Client c's rows keep only client c's lanes.
            xb = jnp.where(x_lanes[0], xb, 0.0)
            for c in range(1, pack):
                xb = jnp.where(x_lanes[c], xg[c * b_pad:(c + 1) * b_pad], xb)

        ws_now = [sw[li][k] for li in range(nl)]
        bs_now = [sb[li][k] for li in range(nl)]
        acts = [xb]
        h = xb
        for li in range(nl):
            h = jnp.dot(h, ws_now[li], preferred_element_type=jnp.float32)
            h = h + bs_now[li]
            if li < nl - 1:
                h = jnp.tanh(h)
            acts.append(h)

        # loss = mean over real rows of sum_j (x - recon)^2; padded batch
        # rows reconstruct the bias stack from a zero input, so mask them.
        diff = (h - xb) * row_mask
        g = (2.0 * inv_b) * diff                         # dL/dz_last
        for li in range(nl - 1, -1, -1):
            a_prev = acts[li]
            dw = jax.lax.dot_general(
                a_prev, g, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if pack > 1:
                # Drop the cross-client terms: off-block weights stay 0.
                dw = jnp.where(diag, dw, 0.0)
            db = jnp.sum(g, axis=0, keepdims=True)
            if li > 0:
                # tanh'(z_{l-1}) = 1 - a_prev^2 (a_prev is the tanh output)
                g = jax.lax.dot_general(
                    g, ws_now[li], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * (1.0 - a_prev * a_prev)
            if mu != 0.0:
                dw = dw + mu * (ws_now[li] - w_refs[li][...])
                db = db + mu * (bs_now[li] - b_refs[li][...])
            sw[li][k] = ws_now[li] - lr * dw
            sb[li][k] = bs_now[li] - lr * db
        return loss_lanes + jnp.sum(diff * diff, axis=0, keepdims=True)

    def step(s, losses):
        # Step s of every pack: the K chains share no value, so the
        # scheduler overlaps them.
        return tuple(sgd_step(k, s, losses[k]) for k in range(packs))

    losses = jax.lax.fori_loop(
        0, steps, step,
        tuple(jnp.zeros((1, d_out), jnp.float32) for _ in range(packs)),
    )

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    for k in range(packs):
        for li in range(nl):
            dw_refs[li][k] = sw[li][k] - w_refs[li][...]
            db_refs[li][k] = sb[li][k] - b_refs[li][...]
        # Lane c of the loss row: client c's squared error summed over its
        # lanes, as a mean over its rows and steps.
        loss_row = jnp.zeros((1, LANES), jnp.float32)
        for c in range(pack):
            own = jnp.where(_in_block((1, d_out), 1, c, width), losses[k], 0.0)
            loss_row = jnp.where(
                lane == c, jnp.sum(own, axis=1, keepdims=True), loss_row
            )
        loss_ref[k] = loss_row * (inv_b / steps)


@functools.partial(
    jax.jit,
    static_argnames=("steps", "batch", "lr", "mu", "interpret", "pack", "width",
                     "packs"),
)
def local_train_blocks(
    x: jax.Array,                  # (N_packs, W_pad, D_pad) f32 packed windows
    idx: jax.Array,                # (N_packs, S_pad, P * B_pad) int32, -1 padding
    ws: tuple[jax.Array, ...],     # block-diagonal weights, (d_in_pad, d_out_pad)
    bs: tuple[jax.Array, ...],     # packed biases, (1, d_out_pad)
    steps: int,                    # real SGD steps (E * nb), <= S_pad
    batch: int,                    # real minibatch rows, <= B_pad
    lr: float,
    mu: float = 0.0,
    interpret: bool = True,
    pack: int = 1,                 # P, clients side by side in each tile
    width: int = LANES,            # Wc, lanes between packed clients
    packs: int = 1,                # K, packs each grid step trains; divides N_packs
) -> tuple[list[jax.Array], list[jax.Array], jax.Array]:
    """Run the fused local-train kernel over packed client tiles.

    Grid = one step per ``packs`` packs of ``pack`` clients, their SGD
    chains interleaved; the broadcast params stay resident across the
    sweep.  Returns (dws [(N_packs, d_in_pad, d_out_pad)] per layer, dbs
    [(N_packs, 1, d_out_pad)] per layer, loss (N_packs, 1, LANES) f32
    whose lane c holds client c's mean local loss) — ops.py cuts each
    client's diagonal block out, slices off the padding and assembles the
    flat ``ravel_pytree``-ordered delta.
    """
    n, w_pad, d_pad = x.shape
    assert w_pad % LANES == 0 and d_pad % LANES == 0, x.shape
    s_pad, pb_pad = idx.shape[1], idx.shape[2]
    assert idx.shape[0] == n and s_pad % SUBLANES == 0, idx.shape
    assert pb_pad % (pack * SUBLANES) == 0, (idx.shape, pack)
    assert 0 < steps <= s_pad and 0 < batch <= pb_pad // pack, (steps, batch)
    assert pack == 1 or all(w.shape == (LANES, LANES) for w in ws), pack
    assert n % packs == 0, (n, packs)

    x_spec = pl.BlockSpec((packs, w_pad, d_pad), lambda i: (i, 0, 0))
    idx_spec = pl.BlockSpec((packs, s_pad, pb_pad), lambda i: (i, 0, 0))
    wb_specs = []
    for w, b in zip(ws, bs):
        wb_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
        wb_specs.append(pl.BlockSpec(b.shape, lambda i: (0, 0)))
    out_specs, out_shape, scratch = [], [], []
    for w, b in zip(ws, bs):
        out_specs.append(pl.BlockSpec((packs, *w.shape), lambda i: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, *w.shape), jnp.float32))
        out_specs.append(pl.BlockSpec((packs, *b.shape), lambda i: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, *b.shape), jnp.float32))
        scratch.append(pltpu.VMEM((packs, *w.shape), jnp.float32))
        scratch.append(pltpu.VMEM((packs, *b.shape), jnp.float32))
    out_specs.append(pl.BlockSpec((packs, 1, LANES), lambda i: (i, 0, 0)))
    out_shape.append(jax.ShapeDtypeStruct((n, 1, LANES), jnp.float32))
    resident, per_pack = pack_vmem_bytes(
        w_pad, d_pad, s_pad, pb_pad, [w.shape for w in ws], pb_pad // pack)

    outs = pl.pallas_call(
        functools.partial(
            _local_train_kernel,
            n_layers=len(ws), steps=steps, batch=batch,
            lr=float(lr), mu=float(mu), pack=pack, width=width,
        ),
        grid=(n // packs,),
        in_specs=[x_spec, idx_spec, *wb_specs],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_fa.vmem_params(resident + packs * per_pack),
        interpret=interpret,
    )(x, idx.astype(jnp.int32), *[a for wb in zip(ws, bs) for a in wb])
    dws = [outs[2 * li] for li in range(len(ws))]
    dbs = [outs[2 * li + 1] for li in range(len(ws))]
    return dws, dbs, outs[-1]
