"""Shared neural-network layers for the architecture zoo.

Explicit init/apply style (dict params, no flax) so the same modules run
under vmap (federated client stacks), scan-over-layers (deep LMs), and
pjit (mesh runtime).  Compute dtype is bf16 with f32 norms/softmax/logits,
the standard TPU recipe.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def shard_hint(x: jax.Array, logical: tuple[str | None, ...]) -> jax.Array:
    """Soft activation-sharding constraint (perf: EXPERIMENTS.md §Perf).

    Resolves ``logical`` dimension names against the AMBIENT mesh (the one
    the launcher/dry-run installed with ``with mesh:``) using the same
    rules as the parameter shardings, and constrains ``x`` to it.  A
    no-op without a mesh, so CPU tests/vmapped federated clients are
    untouched.

    Why: when a head count is not divisible by the model axis (qwen3-14b's
    40 heads, grok's 8 kv heads on a 16-way axis), the parameter fallback
    shards head_dim; without an activation anchor XLA ping-pongs the
    (b, s, h, d) activations between incompatible shardings inside the
    scanned layer body ("involuntary full rematerialization"), inflating
    the collective and memory roofline terms by >5x.  Anchoring q/k/v to
    batch-only (heads replicated when indivisible) keeps the attention
    math local; the only added traffic is the per-layer weight gather.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names or mesh.size <= 1:
        return x
    from repro.launch.sharding import resolve_spec  # no circular import

    # Inside a shard_map manual region (e.g. core/mesh_fl's pod-manual
    # step) sharding constraints on the remaining auto axes trip an XLA
    # SPMD-partitioner CHECK (mixed Manual/Auto groups) — let the
    # partitioner choose freely there instead.
    if jax.sharding.AxisType.Manual in mesh.axis_types:
        return x
    spec = resolve_spec(logical, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in f32, cast back to input dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 style logit soft-capping: cap * tanh(x / cap)."""
    return cap * jnp.tanh(x / cap)


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    """(head_dim/2,) inverse frequencies."""
    exp = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding.  x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)                    # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    angles = angles[..., None, :]                                 # (..., seq, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int) -> jax.Array:
    """Classic transformer sinusoidal table (whisper encoder)."""
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    div = jnp.exp(
        jnp.arange(0, dim, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / dim)
    )
    tab = jnp.zeros((length, dim), jnp.float32)
    tab = tab.at[:, 0::2].set(jnp.sin(pos * div))
    tab = tab.at[:, 1::2].set(jnp.cos(pos * div))
    return tab


def dense_init(key: jax.Array, shape: tuple[int, ...],
               dtype=jnp.bfloat16, scale: float | None = None) -> jax.Array:
    fan_in = shape[0]
    if scale is None:
        scale = fan_in**-0.5
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def embed_init(key: jax.Array, vocab: int, dim: int, dtype=jnp.bfloat16) -> jax.Array:
    # 1/sqrt(d) scale keeps tied-unembedding logits O(1) at init.
    return (dim**-0.5 * jax.random.normal(key, (vocab, dim), jnp.float32)).astype(dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array, act=jax.nn.silu) -> jax.Array:
    """Gated MLP: down( act(x @ gate) * (x @ up) )."""
    g = act(jnp.einsum("...d,df->...f", x, w_gate))
    u = jnp.einsum("...d,df->...f", x, w_up)
    h = g * u
    # Anchor the hidden to (batch, ff): keeps the down-proj a local
    # contraction followed by one model-axis all-reduce of the
    # batch-SHARDED residual shard (EXPERIMENTS.md §Perf iter 2).
    h = shard_hint(h, ("batch",) + (None,) * (h.ndim - 2) + ("ff",))
    out = jnp.einsum("...f,fd->...d", h, w_down)
    return shard_hint(out, ("batch",) + (None,) * (out.ndim - 1))


def gelu_mlp(x: jax.Array, w_in: jax.Array, b_in: jax.Array,
             w_out: jax.Array, b_out: jax.Array) -> jax.Array:
    """Whisper-style biased GELU MLP."""
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, w_in) + b_in)
    return jnp.einsum("...f,fd->...d", h, w_out) + b_out


def chunked_cross_entropy(
    hidden: jax.Array,          # (tokens, d_model)
    unembed: jax.Array,         # (d_model, vocab)
    targets: jax.Array,         # (tokens,) int32
    mask: jax.Array,            # (tokens,) f32
    n_chunks: int = 8,
    softcap_value: float | None = None,
) -> jax.Array:
    """Cross-entropy without materialising full (tokens, vocab) logits.

    Scans over token chunks; each chunk's logits exist only transiently
    (and are recomputed in the backward pass via jax.checkpoint).  This is
    what keeps the 256k-vocab architectures inside HBM at train_4k scale.
    """
    tokens = hidden.shape[0]
    if tokens % n_chunks != 0:
        n_chunks = 1
    chunk = tokens // n_chunks
    h = hidden.reshape(n_chunks, chunk, -1)
    t = targets.reshape(n_chunks, chunk)
    m = mask.reshape(n_chunks, chunk)

    @jax.checkpoint
    def chunk_loss(args):
        hc, tc, mc = args
        logits = jnp.einsum("sd,dv->sv", hc, unembed).astype(jnp.float32)
        if softcap_value is not None:
            logits = softcap(logits, softcap_value)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum((logz - gold) * mc)

    def body(carry, args):
        return carry + chunk_loss(args), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, t, m))
    return total / jnp.maximum(jnp.sum(mask), 1.0)
