"""Update-compression pipeline at the pytree level (paper Sec. V-C).

Two modes:

- ``global``: exact Top-K over the whole flattened update — the paper's
  semantics for the ~1 352-parameter autoencoder (rho_s = 0.05 -> K ~ 68).
- ``blockwise``: the TPU-native blocked kernel path (Deep-Gradient-
  Compression-style per-block selection) for LLM-scale updates, backed by
  the compress-and-aggregate kernel of :mod:`repro.kernels.fused_agg` —
  a single update is one client in one fog at unit weight.

Both apply error feedback (Eq. 30) — the local error buffer absorbs the
sparsification *and* quantisation residuals — and report the acoustic
payload in bits (Eq. 31):  L_u = K (b_q + b_idx).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from repro.kernels import ops as kops


def _concrete(x: Any) -> bool:
    """True when ``x`` is a plain Python number (not a jax value/tracer)."""
    return isinstance(x, (int, float))


@dataclasses.dataclass(frozen=True)
class CompressorConfig:
    """Compression knobs.  ``rho_s`` is a pytree LEAF so sweeps can stack
    several ratios along a config axis and trace them through the pipeline
    (the blockwise kernels select by threshold-bisection against a count,
    so a traced keep-count is supported on the jnp-oracle path); everything
    else — bit-width, mode, backend flags — is static aux data that defines
    the sweep shape-class.

    ``sparse`` is the static sparsity predicate (``rho_s < 1``).  It is
    derived automatically from a concrete ``rho_s`` and carried through
    flatten/unflatten, so code can branch Python-side on ``is_sparse`` /
    ``enabled`` even while ``rho_s`` itself is a tracer.
    """

    rho_s: float | Any = 0.05    # sparsification ratio (1.0 = dense)
    quant_bits: int = 8          # post-sparsification bit-width (32 = none)
    mode: str = "global"         # "global" | "blockwise"
    use_pallas: bool = False     # blockwise only: route through the kernel
    interpret: bool = True       # pallas interpret mode (CPU)
    sparse: bool | None = None   # static rho_s < 1 predicate (None = derive)

    def replace(self, **kw: Any) -> "CompressorConfig":
        # A pytree round-trip pins ``sparse`` to a concrete bool; changing
        # rho_s afterwards must re-derive it or the static predicate goes
        # stale (pass ``sparse`` explicitly to keep a pinned value).
        if "rho_s" in kw and "sparse" not in kw:
            kw["sparse"] = None
        return dataclasses.replace(self, **kw)

    @property
    def is_sparse(self) -> bool:
        if self.sparse is not None:
            return self.sparse
        return bool(self.rho_s < 1.0)

    @property
    def enabled(self) -> bool:
        return self.is_sparse or self.quant_bits < 32


def _cc_flatten(c: CompressorConfig):
    aux = (c.quant_bits, c.mode, c.use_pallas, c.interpret, c.is_sparse)
    return (c.rho_s,), aux


def _cc_unflatten(aux, children) -> CompressorConfig:
    quant_bits, mode, use_pallas, interpret, sparse = aux
    return CompressorConfig(
        rho_s=children[0], quant_bits=quant_bits, mode=mode,
        use_pallas=use_pallas, interpret=interpret, sparse=sparse,
    )


jax.tree_util.register_pytree_node(CompressorConfig, _cc_flatten, _cc_unflatten)


def payload_bits(d: int, cfg: CompressorConfig) -> float | jax.Array:
    """Uplink payload size in bits (paper Eq. 31 / Sec. IV-B).

    ``d`` must be a static (python int) parameter count.  With a concrete
    ``rho_s`` the result is a Python float (exact back-compat); a traced
    ``rho_s`` (config-axis sweeps) yields the identical value as a jnp
    scalar — the branch structure is static either way (``is_sparse``).
    """
    if not cfg.enabled:
        return 32.0 * d
    bits = float(cfg.quant_bits)
    if not cfg.is_sparse:
        return bits * d  # quantise-only: no index overhead
    b_idx = math.ceil(math.log2(max(d, 2)))
    if _concrete(cfg.rho_s):
        k = max(1.0, round(cfg.rho_s * d))
    else:
        k = jnp.maximum(1.0, jnp.round(jnp.asarray(cfg.rho_s, jnp.float32) * d))
    return k * (bits + b_idx)


def blockwise_k_frac(d: int, rho_s: float | jax.Array) -> float | jax.Array:
    """Per-tile keep fraction for blockwise mode on a length-``d`` vector.

    rho_s is a fraction of the REAL coordinates.  The kernels pad the flat
    vector to whole (BLOCK_ELEMS) tiles and keep a uniform k per tile, so
    solve for the k that keeps ~rho_s * d coords total: the tail tile can
    contribute at most its real coordinates (padding zeros never pass the
    magnitude threshold), so when the uniform k exceeds the tail, the full
    tiles must absorb the difference.

    A traced ``rho_s`` (config-axis sweeps) returns the same value as a
    jnp scalar — tile counts stay static, only the keep target traces.
    """
    block = kops.BLOCK_ELEMS
    nb = max(1, -(-d // block))
    tail = d - (nb - 1) * block      # real coords in the last tile
    if _concrete(rho_s):
        target = max(1, round(rho_s * d))
        k = target / nb
        if nb > 1 and k > tail:
            k = (target - tail) / (nb - 1)
        return min(1.0, k / block)
    target = jnp.maximum(1.0, jnp.round(jnp.asarray(rho_s, jnp.float32) * d))
    k = target / nb
    if nb > 1:
        k = jnp.where(k > tail, (target - tail) / (nb - 1), k)
    return jnp.minimum(1.0, k / block)


def validate_blockwise_bits(quant_bits: int) -> None:
    """Blockwise kernels are int8-only; reject widths they would silently
    mis-quantise (4/16-bit configs must use mode='global')."""
    if quant_bits not in (8,) and quant_bits < 32:
        raise ValueError(
            f"blockwise mode supports quant_bits 8 or >=32, got "
            f"{quant_bits}; use mode='global' for other widths"
        )


def init_error(params: Any) -> jax.Array:
    """Zero error-feedback buffer matching the flattened parameter count."""
    flat, _ = ravel_pytree(params)
    return jnp.zeros_like(flat)


def _global_topk_ef(
    v: jax.Array, k: int | jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Exact global Top-K with EF decomposition on a flat vector.

    ``k`` may be traced (config-axis sweeps): the k-th largest magnitude is
    then read out of a full ascending sort at a dynamic index — identical
    threshold, shape-independent of ``k``.
    """
    absv = jnp.abs(v)
    d = absv.shape[0]
    if _concrete(k) or isinstance(k, int):
        kth = jax.lax.top_k(absv, int(k))[0][-1]
    else:
        srt = jnp.sort(absv)                       # ascending
        idx = jnp.clip(d - k.astype(jnp.int32), 0, d - 1)
        kth = jnp.take(srt, idx)                   # == k-th largest
    mask = absv >= kth
    # Tie-break: keep at most k (top_k threshold may admit ties); paper's
    # payload accounting assumes exactly K coords, ties are measure-zero in
    # float updates so a >= mask is the standard implementation.
    sparse = jnp.where(mask, v, 0.0)
    return sparse, v - sparse


def _quantize_global(x: jax.Array, bits: int) -> jax.Array:
    """Symmetric fixed-point quantise/dequantise of nonzeros, global scale."""
    if bits >= 32:
        return x
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(x))
    scale = amax / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -qmax, qmax)
    return jnp.where(scale > 0, q * scale, x)


def compress_update(
    delta: Any, err: jax.Array, cfg: CompressorConfig
) -> tuple[Any, jax.Array]:
    """Compress one client's model update (a pytree).

    Returns (reconstructed_update_tree, new_error_buffer).  The
    reconstruction is what the fog node receives after decode; the error
    buffer stays on the client (Eq. 30).
    """
    flat, unravel = ravel_pytree(delta)
    if not cfg.enabled:
        return delta, err

    if cfg.mode == "global":
        d = flat.shape[0]
        if _concrete(cfg.rho_s):
            k = max(1, int(round(cfg.rho_s * d)))
        else:
            k = jnp.maximum(
                1.0, jnp.round(jnp.asarray(cfg.rho_s, jnp.float32) * d)
            )
        v = flat + err
        if cfg.is_sparse:
            sparse, _ = _global_topk_ef(v, k)
        else:
            sparse = v
        recon = _quantize_global(sparse, cfg.quant_bits)
        new_err = v - recon
        return unravel(recon), new_err

    if cfg.mode == "blockwise":
        # One client in one fog at unit weight: the fog sum is the client's
        # reconstruction.
        validate_blockwise_bits(cfg.quant_bits)
        recon, new_err = kops.compress_aggregate(
            flat[None], err[None], jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.float32), 1,
            blockwise_k_frac(flat.shape[0], cfg.rho_s),
            quantize=cfg.quant_bits < 32,
            use_pallas=cfg.use_pallas, interpret=cfg.interpret,
        )
        return unravel(recon[0]), new_err[0]

    raise ValueError(f"unknown compression mode: {cfg.mode}")


def compression_ratio(d: int, cfg: CompressorConfig) -> float:
    """Effective ratio rho vs uncompressed 32-bit transmission (Sec. V-C)."""
    return payload_bits(d, cfg) / (32.0 * d)
