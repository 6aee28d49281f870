"""Microbenchmark of the fused kernels' jnp-oracle programs on the CPU:
wall time per call and compiled intermediate memory.  On CPU the numbers
establish correctness-path cost only; device times come from the chip
benchmark (``bench/``).

Two tables:

* ``agg_rows``  — the fused compress-and-aggregate op (one program:
  EF Top-K + int8 + weighted fog accumulation) against its sparse-wire
  twin (emit + scatter-accumulate);
* ``local_train_rows`` — the fused local-train solver (the whole E-epoch
  client phase indexing each client's resident window;
  ``optim/sgd.make_client_solver`` for a fusable detector) against the
  per-client ``local_sgd`` scan over a gathered (E * nb, bs, D) batch
  stream (the same autoencoder as a detector that is not fusable), across
  client counts.  The committed JSON is the perf-trend baseline CI
  compares against (benchmarks/check_kernel_micro).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.kernels import ops
from repro.models import autoencoder as ae
from repro.optim.sgd import make_client_solver

# (n_clients, d) cells for the fused aggregate op; n_fog = n_clients // 4.
# The last cell is the 1M-element size (16 * 65536 = 1 048 576).
AGG_SIZES = ((8, 1352), (16, 65536))
K_FRAC = 0.05

# (n_clients, window) cells for the fused local-train solver; feature dim,
# batch size and epochs stay at the paper's Table II values.
LT_SIZES = ((16, 256), (64, 256), (256, 256))
LT_D, LT_BS, LT_EPOCHS, LT_LR = 32, 32, 5, 0.01


def _paired_time(pair, args, reps: int = 16) -> dict[str, float]:
    """Paired-ratio timing for two pipelines over the same inputs: warm
    (compile) both, then time INTERLEAVED single blocked calls with
    alternating within-pair order, and report the MIN of each — the
    estimator the CI perf-trend gate compares.  On a shared
    runner the min is the uncontended cost; means/medians get corrupted
    by multi-call contention storms that hit whichever pipeline is
    unlucky.  ``pair`` is ((name, fn), (name, fn)); fns return a tuple
    whose first leaf supports ``block_until_ready``.
    """
    for _, fn in pair:
        fn(*args)
    times: dict[str, list[float]] = {name: [] for name, _ in pair}
    for rep in range(reps):
        for name, fn in pair if rep % 2 == 0 else pair[::-1]:
            t0 = time.time()
            out = fn(*args)
            out[0].block_until_ready()
            times[name].append((time.time() - t0) * 1e6)
    return {name: min(ts) for name, ts in times.items()}


def _agg_inputs(n_clients: int, d: int):
    key = jax.random.key(n_clients * d)
    deltas = jax.random.normal(key, (n_clients, d))
    errs = jax.random.normal(jax.random.fold_in(key, 1), (n_clients, d)) * 0.1
    n_fog = max(2, n_clients // 4)
    fog_id = jnp.arange(n_clients, dtype=jnp.int32) % n_fog
    weights = jnp.ones((n_clients,), jnp.float32)
    return deltas, errs, fog_id, weights, n_fog


def run(scale: common.Scale) -> dict:
    agg_rows = []
    for n_clients, d in AGG_SIZES:
        deltas, errs, fog_id, weights, n_fog = _agg_inputs(n_clients, d)
        args = (deltas, errs, fog_id, weights)
        fused = lambda D, E, F, W: ops.compress_aggregate(  # noqa: E731
            D, E, F, W, n_fog, K_FRAC, use_pallas=False
        )
        # Sparse-wire twin (PR 10): emit (idx, int8, scale) and
        # scatter-accumulate it — no dense per-client reconstruction.
        wire = lambda D, E, F, W: ops.compress_aggregate_wire(  # noqa: E731
            D, E, F, W, n_fog, K_FRAC, use_pallas=False
        )
        best = _paired_time((("wire", wire), ("fused", fused)), args)

        def _temp_bytes(fn):
            """Peak device memory of the compiled program's INTERMEDIATES
            (``memory_analysis().temp_size_in_bytes``) — the column the
            wire format exists to shrink."""
            compiled = jax.jit(fn).lower(*args).compile()
            return int(compiled.memory_analysis().temp_size_in_bytes)

        agg_rows.append(
            dict(n_clients=n_clients, d=d, elems=n_clients * d, n_fog=n_fog,
                 us_fused_ref=best["fused"], us_wire_ref=best["wire"],
                 temp_fused_bytes=_temp_bytes(fused),
                 temp_wire_bytes=_temp_bytes(wire))
        )

    lt_rows = []
    params = ae.init(jax.random.key(1), LT_D, (16, 8, 16))
    for n_clients, window in LT_SIZES:
        data = jax.random.normal(
            jax.random.key(n_clients), (n_clients, window, LT_D)
        )
        keys = jax.random.split(jax.random.key(2), n_clients)
        fused = jax.jit(make_client_solver(
            ae.loss, batch_size=LT_BS, epochs=LT_EPOCHS, lr=LT_LR
        ))
        scan = jax.jit(make_client_solver(
            dataclasses.replace(ae.detector(), fusable=False),
            batch_size=LT_BS, epochs=LT_EPOCHS, lr=LT_LR,
        ))
        best = _paired_time(
            (("fused", fused), ("scan", scan)), (params, data, keys)
        )
        us_fused, us_scan = best["fused"], best["scan"]
        nb = window // LT_BS
        lt_rows.append(
            dict(n_clients=n_clients, window=window, d_feat=LT_D,
                 epochs=LT_EPOCHS, batch_size=LT_BS,
                 stream_elems=n_clients * LT_EPOCHS * nb * LT_BS * LT_D,
                 us_fused_ref=us_fused, us_scan_ref=us_scan,
                 speedup=us_scan / us_fused)
        )
    return {"agg_rows": agg_rows, "local_train_rows": lt_rows}


def report(res: dict) -> str:
    lines = ["kernel_micro: fused compress-and-aggregate vs sparse wire"
             " (jnp ref path; temp = compiled peak intermediate memory)"]
    lines.append(
        f"{'NxD':>14} {'elems':>9} {'fused us':>10} {'wire us':>9} "
        f"{'tmp f MB':>9} {'tmp w MB':>9}"
    )
    for r in res["agg_rows"]:
        lines.append(
            f"{r['n_clients']:>5}x{r['d']:<8} {r['elems']:>9} "
            f"{r['us_fused_ref']:>10.0f} {r['us_wire_ref']:>9.0f} "
            f"{r['temp_fused_bytes'] / 1e6:>9.2f} "
            f"{r['temp_wire_bytes'] / 1e6:>9.2f}"
        )
    lines.append("fused local-train (resident window) vs per-client scan over"
                 " a gathered batch stream (jnp ref path)")
    lines.append(
        f"{'NxWindow':>14} {'stream':>9} {'fused us':>10} {'scan us':>11} {'speedup':>8}"
    )
    for r in res["local_train_rows"]:
        lines.append(
            f"{r['n_clients']:>5}x{r['window']:<8} {r['stream_elems']:>9} "
            f"{r['us_fused_ref']:>10.0f} {r['us_scan_ref']:>11.0f} "
            f"{r['speedup']:>8.2f}"
        )
    return "\n".join(lines)
