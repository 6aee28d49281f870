"""Compile every main-path Pallas kernel for a described TPU v5e chip.

Interpret mode runs the kernel bodies on the CPU but checks none of the
TPU compiler's rules: block shapes aligned to the (8, 128) tiling, the
scoped-VMEM budget, which primitives Mosaic can lower.  These tests hand
each kernel wrapper in ``kernels/ops.py`` shapes placed on one chip of a
``v5e:2x2`` topology that the installed TPU compiler describes without a
chip attached, compile at the paper's sizes (N = 200 sensors, d = 1,352,
n_fog = 20, D = 32, AE 32-16-8-16-32, rho_s = 0.05, buckets 128 / 1,024;
the wire path also at N = 2,000 in 512-client chunks) and check that the
compiled program holds the kernel as a ``tpu_custom_call``.  Nothing runs.

The topology is described inside the module fixture and never at import,
so every xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import aggregation as agg
from repro.core import compression as comp
from repro.kernels import fused_agg, ops

N, D_MODEL, N_FOG, DIM = 200, 1352, 20, 32
DIMS = (DIM, 16, 8, 16, DIM)
RHO_S = 0.05
K_FRAC = comp.blockwise_k_frac(D_MODEL, RHO_S)
PALLAS = dict(use_pallas=True, interpret=False)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache as cc

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_count(fn, *args) -> int:
    """Compile ``fn`` for the described chip; count its Mosaic kernels."""
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call"
    )


def _ae_params(sh, quantized=False):
    if quantized:
        return [
            {"qw": _spec(sh, (a, b), jnp.int8), "sw": _spec(sh, (1, b)),
             "b": _spec(sh, (b,))}
            for a, b in zip(DIMS, DIMS[1:])
        ]
    return [{"w": _spec(sh, (a, b)), "b": _spec(sh, (b,))}
            for a, b in zip(DIMS, DIMS[1:])]


def _kernel_names(fn, *args) -> list[str]:
    """Compile ``fn`` for the described chip; for each Mosaic kernel, the
    innermost ``jit(<name>)`` before ``pallas_call`` in its op_name (the
    name a profile of the chip attributes the kernel's time to)."""
    names = []
    for line in jax.jit(fn).lower(*args).compile().as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        parts = op_name.group(1).split("/") if op_name else []
        if "pallas_call" in parts:
            jits = [m.group(1) for p in parts[:parts.index("pallas_call")]
                    if (m := re.fullmatch(r"jit\((.*)\)", p))]
            names.append(jits[-1] if jits else "")
    return names


def test_local_train_compiles(one_chip):
    """The packed kernel (the paper AE puts 4 clients in each 128-lane
    tile) compiles at N = 200, at the fleet cell's 512-client chunk and at
    N = 2,000, training 10, 16 and 10 packs in each grid step, and keeps
    its wrapper's name."""
    window, batch, epochs = 256, 32, 5
    steps = epochs * (window // batch)
    assert ops.local_train_pack(DIMS) == 4
    for n, packs in ((N, 10), (512, 16), (2000, 10)):
        with ops.grid_steps_traced() as noted:
            names = _kernel_names(
                lambda p, x, idx: ops.local_train(p, x, idx, 0.01, 0.0, **PALLAS),
                _ae_params(one_chip),
                _spec(one_chip, (n, window, DIM)),
                _spec(one_chip, (n, steps, batch), jnp.int32),
            )
        assert names == ["local_train_blocks"], n
        assert noted["local_train"] == [packs], (n, noted)


@pytest.mark.parametrize("n_fog", [N_FOG, N], ids=["fogs", "identity"])
def test_dense_compress_aggregate_compiles(one_chip, n_fog):
    """``n_fog = N`` is the robust path's per-client segmentation, whose
    resident accumulator needs the raised VMEM limit."""
    n_kernels = _kernel_count(
        lambda dl, e, f, w: ops.compress_aggregate(
            dl, e, f, w, n_fog, K_FRAC, **PALLAS
        ),
        _spec(one_chip, (N, D_MODEL)), _spec(one_chip, (N, D_MODEL)),
        _spec(one_chip, (N,), jnp.int32), _spec(one_chip, (N,)),
    )
    assert n_kernels >= 1


def test_wire_emit_compiles(one_chip):
    n_kernels = _kernel_count(
        lambda dl, e: ops.compress_wire(dl, e, K_FRAC, **PALLAS),
        _spec(one_chip, (N, D_MODEL)), _spec(one_chip, (N, D_MODEL)),
    )
    assert n_kernels >= 1


@pytest.mark.parametrize(
    "n,d",
    [(512, D_MODEL), (8, 4_825_150)],
    ids=["fleet-chunk", "at-chunk"],
)
def test_wire_emit_compiles_at_the_cells_chunks(one_chip, n, d):
    """The fleet cell's 512-client chunk of one block and the Anomaly
    Transformer cell's 8-client chunk of 590 blocks compile with more than
    one tile bisected in each grid step."""
    k_frac = comp.blockwise_k_frac(d, RHO_S)
    with ops.grid_steps_traced() as steps:
        names = _kernel_names(
            lambda dl, e: ops.compress_wire(dl, e, k_frac, **PALLAS),
            _spec(one_chip, (n, d)), _spec(one_chip, (n, d)),
        )
    assert names == ["compress_wire_blocks"]
    tiles = steps["compress"]
    assert len(tiles) == 1 and tiles[0] > 1, tiles


def test_the_cells_compress_shapes_bisect_many_tiles_a_step():
    """train-paper-n200's dense call (200 clients, 20 fogs), its robust
    identity segments, and the wire calls of train-fleet-n50k (512 x 1
    block) and train-at-smd-n200 (8 x 590 blocks) never take one tile a
    step."""
    kp_ae = fused_agg.slot_pad(ops.wire_k(K_FRAC))
    kp_at = fused_agg.slot_pad(ops.wire_k(comp.blockwise_k_frac(4_825_150, RHO_S)))
    assert fused_agg.dense_tiles_per_step(N, N_FOG) > 1
    assert fused_agg.dense_tiles_per_step(N, N) > 1
    assert fused_agg.wire_tiles_per_step(512, 1, kp_ae) > 1
    assert fused_agg.wire_tiles_per_step(8, 590, kp_at) > 1


def test_wire_aggregate_compiles(one_chip):
    k = ops.wire_k(K_FRAC)
    n_kernels = _kernel_count(
        lambda i, q, s, f, w: ops.wire_aggregate(
            i, q, s, f, w, N_FOG, D_MODEL, **PALLAS
        ),
        _spec(one_chip, (N, 1, k), jnp.int32), _spec(one_chip, (N, 1, k)),
        _spec(one_chip, (N, 1)), _spec(one_chip, (N,), jnp.int32),
        _spec(one_chip, (N,)),
    )
    assert n_kernels >= 1


def test_chunked_wire_round_compiles(one_chip):
    """N = 2,000 in 512-client chunks: the scan body emits and consumes
    the sparse wire, one kernel each."""
    n = 2000
    cfg = comp.CompressorConfig(
        rho_s=RHO_S, mode="blockwise", use_pallas=True, interpret=False
    )
    n_kernels = _kernel_count(
        lambda dl, e, f, w: agg.compress_and_accumulate(
            dl, e, f, w, N_FOG, cfg, chunk=512
        ),
        _spec(one_chip, (n, D_MODEL)), _spec(one_chip, (n, D_MODEL)),
        _spec(one_chip, (n,), jnp.int32), _spec(one_chip, (n,)),
    )
    assert n_kernels >= 2


@pytest.mark.parametrize("mode", ["trimmed", "median"])
def test_robust_aggregate_compiles(one_chip, mode):
    n_kernels = _kernel_count(
        lambda r, f, w: ops.robust_aggregate(
            r, f, w, N_FOG, 0.1, mode, **PALLAS
        ),
        _spec(one_chip, (N, D_MODEL)), _spec(one_chip, (N,), jnp.int32),
        _spec(one_chip, (N,)),
    )
    assert n_kernels >= 1


@pytest.mark.parametrize("bucket", [128, 1024])
@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_fused_score_compiles(one_chip, bucket, quantized):
    score = ops.fused_score_q8 if quantized else ops.fused_score
    n_kernels = _kernel_count(
        lambda p, x, t: score(x, p, t, **PALLAS),
        _ae_params(one_chip, quantized),
        _spec(one_chip, (bucket, DIM)), _spec(one_chip, (bucket,)),
    )
    assert n_kernels >= 1
