"""Per-kernel Pallas (interpret=True) vs pure-jnp oracle sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SIZES = [64, 1024, 1352, 4096, 8192 + 17, 65536]


def _one_client(delta, err, k_frac, quantize, **kw):
    """One client in its own fog at unit weight: the fog sum is its
    reconstruction."""
    fog_sum, new_err = ops.compress_aggregate(
        delta[None], err[None], jnp.zeros((1,), jnp.int32), jnp.ones((1,)),
        1, k_frac, quantize=quantize, **kw,
    )
    return fog_sum[0], new_err[0]


@pytest.mark.parametrize("n", SIZES)
def test_wire_codes_and_scales_match_ref(n):
    """The wire's int8 codes and per-block scales (Pallas, interpret mode)
    are kernels/ref.compress_ref's, placed at the wire's slot indices."""
    key = jax.random.key(n + 1)
    delta = jax.random.normal(key, (2, n))
    err = jax.random.normal(jax.random.fold_in(key, 1), (2, n)) * 0.1
    idx, q, scale, _ = ops.compress_wire(
        delta, err, 0.05, quantize=True, use_pallas=True, interpret=True
    )
    nb = max(1, -(-n // ops.BLOCK_ELEMS))
    blocks = [jnp.pad(a, ((0, 0), (0, nb * ops.BLOCK_ELEMS - n))).reshape(2, nb, -1)
              for a in (delta, err)]
    q_r, s_r, _ = jax.vmap(lambda x, e: ref.compress_ref(x, e, ops.wire_k(0.05)))(*blocks)
    # Padding slots carry code 0, so scattering the slots rebuilds the block.
    dense = jnp.zeros(q_r.shape, jnp.float32).at[
        jnp.arange(2)[:, None, None], jnp.arange(nb)[None, :, None], idx].add(q)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(q_r, np.float32))
    np.testing.assert_allclose(np.asarray(scale), np.asarray(s_r[..., 0]), rtol=1e-6)


def test_compress_ef_identity():
    """recon + err' == delta + err up to int8 rounding (absorbed in err')."""
    n = 4096
    delta = jax.random.normal(jax.random.key(0), (n,))
    err = jnp.zeros((n,))
    recon, new_err = _one_client(
        delta, err, 0.05, True, use_pallas=True, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(recon + new_err), np.asarray(delta), atol=1e-5
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_ef_dtypes(dtype):
    """Top-K-only compression of bf16 updates: the kernel matches the
    oracle to bf16 precision."""
    n = 2048
    delta = jax.random.normal(jax.random.key(5), (n,)).astype(dtype)
    err = jnp.zeros((n,), dtype)
    s_p, _ = _one_client(delta, err, 0.05, False, use_pallas=True, interpret=True)
    s_r, _ = _one_client(delta, err, 0.05, False, use_pallas=False)
    atol = 1e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(s_p, np.float32), np.asarray(s_r, np.float32), atol=atol
    )


def _swa_batched(q, k_cache, v_cache, cache_len, window, **kw):
    """The kernel is per-sequence (hq, d) x (s, hkv, d); batch via vmap,
    exactly how models/attention.py drives it."""
    return jax.vmap(
        lambda qq, kk, vv, ln: ops.swa_decode_attention(
            qq, kk, vv, ln, window, **kw
        )
    )(q, k_cache, v_cache, cache_len)


@pytest.mark.parametrize("heads,kv_heads,head_dim", [(8, 8, 64), (8, 2, 64), (4, 1, 128)])
@pytest.mark.parametrize("window", [64, 256])
def test_swa_decode_attention_matches_ref(heads, kv_heads, head_dim, window):
    batch, max_seq = 2, 512
    key = jax.random.key(heads * window)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (batch, heads, head_dim))
    k_cache = jax.random.normal(ks[1], (batch, max_seq, kv_heads, head_dim))
    v_cache = jax.random.normal(ks[2], (batch, max_seq, kv_heads, head_dim))
    cache_len = jnp.array([300, 77], jnp.int32)
    out_p = _swa_batched(
        q, k_cache, v_cache, cache_len, window, use_pallas=True, interpret=True
    )
    out_r = _swa_batched(
        q, k_cache, v_cache, cache_len, window, use_pallas=False
    )
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(out_r), atol=2e-5, rtol=1e-4
    )


def test_swa_attention_respects_window():
    """Tokens outside the sliding window must not affect the output."""
    batch, heads, kv_heads, head_dim, max_seq, window = 1, 4, 4, 32, 512, 64
    key = jax.random.key(9)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (batch, heads, head_dim))
    k_cache = jax.random.normal(ks[1], (batch, max_seq, kv_heads, head_dim))
    v_cache = jax.random.normal(ks[2], (batch, max_seq, kv_heads, head_dim))
    cache_len = jnp.array([200], jnp.int32)
    out1 = _swa_batched(q, k_cache, v_cache, cache_len, window)
    # Corrupt everything outside [cache_len - window, cache_len)
    k2 = k_cache.at[:, : 200 - window].set(99.0)
    v2 = v_cache.at[:, : 200 - window].set(-99.0)
    k2 = k2.at[:, 200:].set(99.0)
    v2 = v2.at[:, 200:].set(-99.0)
    out2 = _swa_batched(q, k2, v2, cache_len, window)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)
