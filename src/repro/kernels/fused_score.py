"""Pallas TPU kernel: fused anomaly scoring (AE forward + reconstruction
error + threshold compare) — the serving hot path in ONE pass.

The unfused serving pipeline makes three HBM round-trips per telemetry
batch: the autoencoder forward writes a dense (R, d) reconstruction, the
error reduction re-reads it (and the input) to produce the per-sample
squared-L2 errors, and the threshold compare re-reads those.  This kernel
loads each row tile once, runs encode -> decode -> error -> compare
entirely in VMEM (bit-compatible with :func:`repro.kernels.ref.
fused_score_ref`, i.e. ``models/autoencoder.apply`` semantics), and writes
only the (R,) errors and flags — the dense reconstruction never exists in
HBM.

Layout: ops.py pads the row count to a multiple of SCORE_ROWS and every
layer dimension (the feature dim included) to a multiple of LANES = 128,
zero-filling weights/biases.  Zero padding is exact: tanh(0) = 0, padded
weight rows/columns contribute nothing, and padded feature columns add
(0 - 0)^2 to the error.  The grid runs one step per row tile; the padded
layer parameters ride along as whole-array blocks (index map pinned to the
origin) so they stay resident in VMEM across the whole sweep — at the
paper's 32-16-8-16-32 autoencoder that is four 128x128 f32 matrices,
~256 KiB next to a 64 KiB row tile.  Each (SCORE_ROWS, 128) @ (128, 128)
layer step is MXU-shaped.  Thresholds arrive pre-broadcast per row (the
serving layer maps per-fog taus onto rows), tiled (1, SCORE_ROWS) like the
outputs: the per-row vectors live as ``(nb, 1, SCORE_ROWS)`` arrays whose
``(1, SCORE_ROWS)`` blocks span the full array in their last two dims, the
layout Mosaic accepts for any tile count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SCORE_ROWS = 128   # telemetry rows per grid step
LANES = 128        # layer-dimension padding unit (VPU lane count)


def _fused_score_kernel(x_ref, tau_ref, *refs, n_layers: int):
    err_ref, flag_ref = refs[-2], refs[-1]
    x = x_ref[...].astype(jnp.float32)            # (SCORE_ROWS, d_pad)
    h = x
    for li in range(n_layers):
        w = refs[2 * li][...]                     # (d_in_pad, d_out_pad)
        b = refs[2 * li + 1][...]                 # (1, d_out_pad)
        h = jnp.dot(h, w, preferred_element_type=jnp.float32) + b
        if li < n_layers - 1:
            h = jnp.tanh(h)
    diff = x - h
    err = jnp.sum(diff * diff, axis=-1)[None, None, :]   # (1, 1, SCORE_ROWS)
    err_ref[...] = err
    flag_ref[...] = (err > tau_ref[...]).astype(jnp.float32)


def _fused_score_q8_kernel(x_ref, tau_ref, *refs, n_layers: int):
    """int8-weight variant: each layer ships (q int8, scale (1, d_out),
    bias) and is dequantised per output channel IN VMEM right before its
    matmul — HBM (and the resident weight blocks) only ever hold int8,
    a 4x cut of the weight bytes that stay live across the row sweep."""
    err_ref, flag_ref = refs[-2], refs[-1]
    x = x_ref[...].astype(jnp.float32)            # (SCORE_ROWS, d_pad)
    h = x
    for li in range(n_layers):
        q = refs[3 * li][...]                     # (d_in_pad, d_out_pad) i8
        s = refs[3 * li + 1][...]                 # (1, d_out_pad) f32
        b = refs[3 * li + 2][...]                 # (1, d_out_pad) f32
        w = q.astype(jnp.float32) * s             # per-channel dequant
        h = jnp.dot(h, w, preferred_element_type=jnp.float32) + b
        if li < n_layers - 1:
            h = jnp.tanh(h)
    diff = x - h
    err = jnp.sum(diff * diff, axis=-1)[None, None, :]   # (1, 1, SCORE_ROWS)
    err_ref[...] = err
    flag_ref[...] = (err > tau_ref[...]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def score_blocks_q8(
    x: jax.Array,                  # (R_pad, d_pad) f32, R_pad % SCORE_ROWS == 0
    tau: jax.Array,                # (nb, 1, SCORE_ROWS) f32 (+inf on padded rows)
    qws: tuple[jax.Array, ...],    # padded int8 weights, (d_in_pad, d_out_pad)
    sws: tuple[jax.Array, ...],    # padded scales, (1, d_out_pad) f32
    bs: tuple[jax.Array, ...],     # padded biases, (1, d_out_pad) f32
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused score sweep with int8-resident weights (see the q8 kernel).

    Same grid/layout contract as :func:`score_blocks`; zero-padded int8
    weight rows/columns dequantise to exact zeros (0 * scale), so padding
    stays exact."""
    r_pad, d_pad = x.shape
    assert r_pad % SCORE_ROWS == 0 and d_pad % LANES == 0, x.shape
    nb = r_pad // SCORE_ROWS
    assert tau.shape == (nb, 1, SCORE_ROWS), tau.shape

    x_spec = pl.BlockSpec((SCORE_ROWS, d_pad), lambda i: (i, 0))
    row_spec = pl.BlockSpec((1, 1, SCORE_ROWS), lambda i: (i, 0, 0))
    wb_specs = []
    for q, s, b in zip(qws, sws, bs):
        wb_specs.append(pl.BlockSpec(q.shape, lambda i: (0, 0)))
        wb_specs.append(pl.BlockSpec(s.shape, lambda i: (0, 0)))
        wb_specs.append(pl.BlockSpec(b.shape, lambda i: (0, 0)))
    return pl.pallas_call(
        functools.partial(_fused_score_q8_kernel, n_layers=len(qws)),
        grid=(nb,),
        in_specs=[x_spec, row_spec, *wb_specs],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, SCORE_ROWS), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, SCORE_ROWS), jnp.float32),
        ],
        interpret=interpret,
    )(x, tau, *[a for qsb in zip(qws, sws, bs) for a in qsb])


@functools.partial(jax.jit, static_argnames=("interpret",))
def score_blocks(
    x: jax.Array,                  # (R_pad, d_pad) f32, R_pad % SCORE_ROWS == 0
    tau: jax.Array,                # (nb, 1, SCORE_ROWS) f32 (+inf on padded rows)
    ws: tuple[jax.Array, ...],     # padded weights, (d_in_pad, d_out_pad)
    bs: tuple[jax.Array, ...],     # padded biases, (1, d_out_pad)
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Run the fused score kernel over padded row tiles.

    Returns (err (nb, 1, SCORE_ROWS) f32, flag (nb, 1, SCORE_ROWS) f32 0/1 —
    float so every output block shares the f32 tiling; ops.py casts back
    to bool after unpadding).
    """
    r_pad, d_pad = x.shape
    assert r_pad % SCORE_ROWS == 0 and d_pad % LANES == 0, x.shape
    nb = r_pad // SCORE_ROWS
    assert tau.shape == (nb, 1, SCORE_ROWS), tau.shape

    x_spec = pl.BlockSpec((SCORE_ROWS, d_pad), lambda i: (i, 0))
    row_spec = pl.BlockSpec((1, 1, SCORE_ROWS), lambda i: (i, 0, 0))
    wb_specs = []
    for w, b in zip(ws, bs):
        wb_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
        wb_specs.append(pl.BlockSpec(b.shape, lambda i: (0, 0)))
    return pl.pallas_call(
        functools.partial(_fused_score_kernel, n_layers=len(ws)),
        grid=(nb,),
        in_specs=[x_spec, row_spec, *wb_specs],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, SCORE_ROWS), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, SCORE_ROWS), jnp.float32),
        ],
        interpret=interpret,
    )(x, tau, *[a for wb in zip(ws, bs) for a in wb])
