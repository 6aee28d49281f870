"""Pallas TPU kernel: fused local training — one client's ENTIRE federated
work item (E epochs of minibatch SGD on the paper autoencoder, Eq. 12) in a
single VMEM-resident launch.

The unfused client phase is the last big HBM spender in the round loop:
``data/pipeline.multi_epoch_batches`` gathers a dense ``(E * nb, bs, D)``
batch stream per client per round (``E * nb * bs`` rows re-read from a
``window``-row buffer), and ``optim/sgd.local_sgd`` then scans one
``value_and_grad`` + tree-update per minibatch over it — on the engine's
``(seed, deployment)`` trial grid that is ``O(S * P * N * E * window * D)``
gather traffic before a single useful FLOP.  This kernel instead keeps ONE
copy of the client's ``(window, D)`` window and the broadcast params
resident in VMEM for the whole local phase: each grid step (= one client)
loads its window once, then for every minibatch *indexes* the resident
rows (a one-hot selector matmul — the TPU-native gather), runs forward +
manual backward + the SGD/FedProx update fused, and finally writes only the
per-layer parameter DELTAS ``theta_i^E - theta^t`` and the mean loss.  The
dense batch stream never exists anywhere; only the tiny ``(steps, bs)``
int32 permutation table (from ``data/pipeline.multi_epoch_indices``) rides
along, so the client phase chains straight into the fused
compress-and-aggregate kernel and the whole sensor side of a round is two
launches with no dense intermediates.

Layout: ops.py pads the window and every layer dimension (feature dim
included) to LANES = 128 and the (steps, batch) index table to SUBLANES
= 8 on both axes, zero-filling data/weights/biases and -1-filling index
padding; each SGD step reads its index row with a dynamic ref slice.
Zero padding is exact end to end: padded window rows are never selected (indices only address
real rows), padded batch rows select nothing (all-zero one-hot row) and
are masked out of the loss/gradient, and padded layer lanes stay
identically zero through forward, backward, and the update (tanh(0) = 0,
zero weight rows/columns propagate zeros, so the emitted deltas are zero
there).  The broadcast params ride as whole-array blocks with the index
map pinned to the origin — resident across all N sequential client steps
— and per-client working params live in VMEM scratch, re-seeded from the
broadcast blocks at each grid step.  At the paper's 32-16-8-16-32
autoencoder that is four 128x128 f32 anchor matrices + the same again in
scratch (~512 KiB) next to a (window, 128) data tile.  Every per-step
matmul — the one-hot gather, the four layer GEMMs, and their transposed
backward partners — is MXU-shaped.

FedProx (``mu > 0``) is free here: the anchor ``theta^t`` the proximal
term needs is exactly the resident broadcast block, so the kernel adds
``mu * (theta - anchor)`` to the gradient without any extra traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128      # layer-dimension / window padding unit (VPU lane count)
SUBLANES = 8     # batch-row padding unit (f32 sublane count)


def _local_train_kernel(
    x_ref,        # (1, W_pad, D_pad) this client's data window
    idx_ref,      # (1, S_pad, B_pad) int32 minibatch indices, -1 = padding
    *refs,
    n_layers: int,
    steps: int,
    batch: int,
    lr: float,
    mu: float,
):
    nl = n_layers
    w_refs = [refs[2 * li] for li in range(nl)]          # anchor theta^t
    b_refs = [refs[2 * li + 1] for li in range(nl)]
    outs = refs[2 * nl:]
    dw_refs = [outs[2 * li] for li in range(nl)]
    db_refs = [outs[2 * li + 1] for li in range(nl)]
    loss_ref = outs[2 * nl]
    scratch = outs[2 * nl + 1:]
    sw = [scratch[2 * li] for li in range(nl)]           # working theta
    sb = [scratch[2 * li + 1] for li in range(nl)]

    # Re-seed the working params from the resident broadcast blocks: every
    # client starts its local phase from the same global theta^t.
    for li in range(nl):
        sw[li][...] = w_refs[li][...]
        sb[li][...] = b_refs[li][...]

    x = x_ref[0]                                         # (W_pad, D_pad)
    w_pad = x.shape[0]
    b_pad = idx_ref.shape[2]
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (w_pad, b_pad), 0)
    ones_x = jnp.ones_like(x)
    inv_b = 1.0 / batch
    tn = (((0,), (0,)), ((), ()))                        # contract dim 0

    def step(s, loss_sum):
        idx_row = idx_ref[0, pl.ds(s, 1), :]             # (1, B_pad) int32
        # Gather-as-matmul: the transposed one-hot selector (one column per
        # batch row) picks the minibatch out of the resident window; padded
        # batch rows select nothing.  HIGHEST keeps the gather exact (a
        # default-precision f32 dot may round the data to bf16).
        sel_t = (iota_w == idx_row).astype(jnp.float32)  # (W_pad, B_pad)
        xb = jax.lax.dot_general(
            sel_t, x, tn, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                                # (B_pad, D_pad)
        # 1.0 on real batch rows, 0.0 on padding (exact 0/1 sums).
        row_mask = jax.lax.dot_general(
            sel_t, ones_x, tn, preferred_element_type=jnp.float32,
        )

        ws_now = [sw[li][...] for li in range(nl)]
        bs_now = [sb[li][...] for li in range(nl)]
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
        loss = jnp.sum(diff * diff) * inv_b
        g = (2.0 * inv_b) * diff                         # dL/dz_last
        for li in range(nl - 1, -1, -1):
            a_prev = acts[li]
            dw = jax.lax.dot_general(
                a_prev, g, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
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
            sw[li][...] = ws_now[li] - lr * dw
            sb[li][...] = bs_now[li] - lr * db
        return loss_sum + loss

    loss_sum = jax.lax.fori_loop(0, steps, step, jnp.float32(0.0))

    for li in range(nl):
        dw_refs[li][0] = sw[li][...] - w_refs[li][...]
        db_refs[li][0] = sb[li][...] - b_refs[li][...]
    loss_ref[...] = jnp.full(loss_ref.shape, loss_sum / steps, jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("steps", "batch", "lr", "mu", "interpret")
)
def local_train_blocks(
    x: jax.Array,                  # (N, W_pad, D_pad) f32 client windows
    idx: jax.Array,                # (N, S_pad, B_pad) int32, -1 padding
    ws: tuple[jax.Array, ...],     # padded weights, (d_in_pad, d_out_pad)
    bs: tuple[jax.Array, ...],     # padded biases, (1, d_out_pad)
    steps: int,                    # real SGD steps (E * nb), <= S_pad
    batch: int,                    # real minibatch rows, <= B_pad
    lr: float,
    mu: float = 0.0,
    interpret: bool = True,
) -> tuple[list[jax.Array], list[jax.Array], jax.Array]:
    """Run the fused local-train kernel over padded per-client tiles.

    Grid = one step per client; the broadcast params stay resident across
    the sweep.  Returns (dws [(N, d_in_pad, d_out_pad)] per layer,
    dbs [(N, 1, d_out_pad)] per layer, loss (N, 1, LANES) f32, the mean
    local loss broadcast along lanes so its ``(1, LANES)`` block spans
    the array's last two dims) — ops.py slices off the padding and
    assembles the flat ``ravel_pytree``-ordered delta.
    """
    n, w_pad, d_pad = x.shape
    assert w_pad % LANES == 0 and d_pad % LANES == 0, x.shape
    s_pad, b_pad = idx.shape[1], idx.shape[2]
    assert idx.shape[0] == n and s_pad % SUBLANES == 0, idx.shape
    assert 0 < steps <= s_pad and 0 < batch <= b_pad, (steps, batch)

    x_spec = pl.BlockSpec((1, w_pad, d_pad), lambda i: (i, 0, 0))
    idx_spec = pl.BlockSpec((1, s_pad, b_pad), lambda i: (i, 0, 0))
    wb_specs = []
    for w, b in zip(ws, bs):
        wb_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
        wb_specs.append(pl.BlockSpec(b.shape, lambda i: (0, 0)))
    out_specs, out_shape, scratch = [], [], []
    for w, b in zip(ws, bs):
        out_specs.append(pl.BlockSpec((1, *w.shape), lambda i: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, *w.shape), jnp.float32))
        out_specs.append(pl.BlockSpec((1, *b.shape), lambda i: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, *b.shape), jnp.float32))
        scratch.append(pltpu.VMEM(w.shape, jnp.float32))
        scratch.append(pltpu.VMEM(b.shape, jnp.float32))
    out_specs.append(pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0)))
    out_shape.append(jax.ShapeDtypeStruct((n, 1, LANES), jnp.float32))

    outs = pl.pallas_call(
        functools.partial(
            _local_train_kernel,
            n_layers=len(ws), steps=steps, batch=batch,
            lr=float(lr), mu=float(mu),
        ),
        grid=(n,),
        in_specs=[x_spec, idx_spec, *wb_specs],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(x, idx.astype(jnp.int32), *[a for wb in zip(ws, bs) for a in wb])
    dws = [outs[2 * li] for li in range(len(ws))]
    dbs = [outs[2 * li + 1] for li in range(len(ws))]
    return dws, dbs, outs[-1]
