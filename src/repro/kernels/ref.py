"""Pure-jnp oracles for every Pallas kernel in this package.

These define the *semantics* the kernels must match bit-for-bit (or to
float tolerance where reductions reorder).  Tests sweep shapes/dtypes and
``assert_allclose`` kernel-vs-oracle.

Semantics notes
---------------
Block Top-K uses *threshold-by-bisection* selection: a per-block magnitude
threshold t is refined for a fixed number of iterations so that the number
of entries with |x| > t is as large as possible while <= k.  This is the
TPU-native replacement for CUDA radix-select (see DESIGN.md §4); the oracle
implements the identical iteration so kernel and oracle agree exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BISECT_ITERS = 32


def bisect_threshold(
    absx: jax.Array, k: int, iters: int = BISECT_ITERS,
    hi: jax.Array | None = None,
) -> jax.Array:
    """Magnitude threshold t with |{i : absx_i > t}| <= k, maximal keep.

    ``absx``: (..., block) non-negative.  Returns (..., 1) threshold.
    Invariant maintained: count(> hi) <= k <= count(> lo)  (lo starts at -1
    so every entry passes; hi starts at max so none does).  Callers that
    already hold the per-block max can pass it as ``hi`` to skip the
    reduction.
    """
    lo = jnp.full(absx.shape[:-1] + (1,), -1.0, absx.dtype)
    if hi is None:
        hi = jnp.max(absx, axis=-1, keepdims=True)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum(absx > mid, axis=-1, keepdims=True)
        lo = jnp.where(cnt > k, mid, lo)
        hi = jnp.where(cnt > k, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return hi


def blockwise_topk_ef_ref(
    delta: jax.Array, err: jax.Array, k_per_block: int
) -> tuple[jax.Array, jax.Array]:
    """Error-feedback block Top-K (paper Eq. 30, blockwise TPU variant).

    Inputs are (nb, block).  Returns (sparse, new_err) with
    sparse + new_err == delta + err exactly (mask decomposition).
    """
    v = delta + err
    absv = jnp.abs(v)
    t = bisect_threshold(absv, k_per_block)
    mask = absv > t
    sparse = jnp.where(mask, v, 0.0)
    return sparse, v - sparse


def quant8_ref(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-block symmetric int8 quantisation.

    x: (nb, block) -> (q int8 (nb, block), scale f32 (nb, 1));
    scale = max|x| / 127, q = round(x / scale).  All-zero blocks get
    scale 0 and q 0.
    """
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -127, 127).astype(jnp.int8)
    q = jnp.where(scale > 0, q, jnp.zeros_like(q))
    return q, scale


def dequant8_ref(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quant8_ref` (lossy)."""
    return q.astype(jnp.float32) * scale


def compress_ref(
    delta: jax.Array, err: jax.Array, k_per_block: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused EF Top-K + int8 quantisation (the full paper pipeline, Sec. V-C).

    Returns (q int8, scale, new_err).  The error buffer absorbs *both* the
    sparsification residual and the quantisation residual, so no update
    information is permanently lost:
        dequant(q, scale) + new_err == delta + err   (up to f32 rounding)
    """
    v = delta + err
    absv = jnp.abs(v)
    t = bisect_threshold(absv, k_per_block)
    mask = absv > t
    sparse = jnp.where(mask, v, 0.0)
    q, scale = quant8_ref(sparse)
    recon = dequant8_ref(q, scale)
    return q, scale, v - recon


def compress_aggregate_ref(
    delta: jax.Array,        # (N, nb, block) per-client blocked updates
    err: jax.Array,          # (N, nb, block) EF buffers
    fog_id: jax.Array,       # (N,) int32 cluster id per client
    weights: jax.Array,      # (N,) f32, zeroed for non-participants
    n_fog: int,
    k_per_block: int,
    quantize: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the fused compress-and-aggregate kernel.

    Per client: EF Top-K (+ optional int8 round-trip), exactly the
    :func:`compress_ref` / :func:`blockwise_topk_ef_ref` semantics; the
    reconstructions are then weight-scaled and segment-summed into per-fog
    accumulators instead of being returned densely.

    Returns (fog_sum (n_fog, nb, block) f32 — the UNNORMALISED weighted
    sums sum_{i in C_m} w_i recon_i — and new_err (N, nb, block)).
    """
    v = delta + err
    absv = jnp.abs(v)
    amax = jnp.max(absv, axis=-1, keepdims=True)
    t = bisect_threshold(absv, k_per_block, hi=amax)
    sparse = jnp.where(absv > t, v, 0.0)
    if quantize:
        # int8 round-trip in f32: round() yields exact integers <= 127, so
        # q * scale is bit-identical to quant8_ref + dequant8_ref without
        # materialising the int8 codes (the fused op never transmits them).
        # The quantisation scale reuses the block max of absv: whenever any
        # coordinate survives the threshold the block max survives too
        # (absv_max > t), so max|sparse| == max(absv); when nothing
        # survives, sparse is all-zero and the scale multiplies only
        # zeros — recon/new_err are identical either way.
        scale = amax / 127.0
        safe = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(sparse / safe), -127.0, 127.0)
        recon = jnp.where(scale > 0, q * scale, 0.0)
    else:
        recon = sparse
    # Cluster reduction as a one-hot GEMM with the weights folded into the
    # selector: no dense (N, nb, block) weighted intermediate, no scatter.
    sel = jnp.where(
        fog_id[None, :] == jnp.arange(n_fog)[:, None], weights[None, :], 0.0
    ).astype(jnp.float32)
    fog_sum = jnp.tensordot(sel, recon.astype(jnp.float32), axes=(1, 0))
    return fog_sum, v - recon


def compress_wire_ref(
    delta: jax.Array,        # (N, nb, block) per-client blocked updates
    err: jax.Array,          # (N, nb, block) EF buffers
    k_per_block: int,
    quantize: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Emit the sparse wire format: what actually travels up the acoustic link.

    Selection is the identical bisection-threshold rule as
    :func:`compress_aggregate_ref` (mask = |v| > t), but instead of a dense
    masked array the survivors are packed into ``k_per_block`` fixed slots
    per block, in ascending coordinate order.  Returns

    - ``idx``   (N, nb, k) int32 — within-block coordinate of each slot;
      slots past the survivor count carry index 0,
    - ``q``     (N, nb, k) int8 (``quantize``) or f32 — slot values; unused
      slots carry value 0, making them no-ops for any consumer that
      scatter-adds,
    - ``scale`` (N, nb) f32 — per-block dequant scale (block max / 127;
      1.0 when not quantizing so ``q * scale`` is always the recon),
    - ``new_err`` (N, nb, block) — EF state, bit-identical to the dense
      path's (the residual decomposition is the same).

    The wire is the rho_s-sized object: per block it is k indices + k int8
    codes + one f32 scale, the Eq. 31 payload made manifest instead of
    analytic-only.
    """
    v = delta + err
    absv = jnp.abs(v)
    amax = jnp.max(absv, axis=-1, keepdims=True)
    t = bisect_threshold(absv, k_per_block, hi=amax)
    survive = absv > t
    block = v.shape[-1]
    k = min(int(k_per_block), block)
    # Survivors first in ascending coordinate order, then the rest: the
    # top-k of a key that is -coord for survivors and below -block
    # otherwise.  Bisection guarantees <= k_per_block survivors, so every
    # survivor lands in a slot; surplus slots are zeroed below.
    coord = jnp.arange(block, dtype=jnp.float32)
    rank_key = jnp.where(survive, -coord, -block - coord)
    _, idx = jax.lax.top_k(rank_key, k)
    kept = jnp.take_along_axis(survive, idx, axis=-1)
    vals = jnp.where(kept, jnp.take_along_axis(v, idx, axis=-1), 0.0)
    idx = jnp.where(kept, idx, 0)
    if quantize:
        # Same scale rule as compress_aggregate_ref: block max of absv (the
        # top survivor IS the block max whenever anything survives).
        scale = (amax / 127.0)[..., 0]                      # (N, nb)
        safe = jnp.where(scale > 0, scale, 1.0)[..., None]
        q = jnp.clip(jnp.round(vals / safe), -127.0, 127.0)
        recon_vals = jnp.where(scale[..., None] > 0, q * scale[..., None], 0.0)
        q = q.astype(jnp.int8)
    else:
        scale = jnp.ones(v.shape[:-1], jnp.float32)
        q = vals
        recon_vals = vals
    n, nb, _ = v.shape
    ii = jnp.arange(n)[:, None, None]
    bb = jnp.arange(nb)[None, :, None]
    new_err = v.at[ii, bb, idx].add(-recon_vals)
    return idx.astype(jnp.int32), q, scale, new_err


def wire_aggregate_ref(
    idx: jax.Array,          # (N, nb, k) int32 within-block coordinates
    q: jax.Array,            # (N, nb, k) int8 codes (or f32 values)
    scale: jax.Array,        # (N, nb) f32 per-block dequant scales
    fog_id: jax.Array,       # (N,) int32 cluster id per client
    weights: jax.Array,      # (N,) f32, zeroed for non-participants
    n_fog: int,
    block: int,
) -> jax.Array:
    """Weighted scatter-accumulate straight off the wire.

    Each slot contributes ``w_i * q * scale`` at its block coordinate of its
    client's fog accumulator.  No dense (N, nb, block) reconstruction ever
    exists — contributions flow (N, nb, k) -> (n_fog, nb, block) directly,
    which is what bounds the memory high-water mark at fleet scale.
    Returns fog_sum (n_fog, nb, block) f32 (unnormalised weighted sums).
    """
    n, nb, _ = idx.shape
    contrib = q.astype(jnp.float32) * scale[..., None] * weights[:, None, None]
    ff = jnp.broadcast_to(fog_id[:, None, None], idx.shape)
    bb = jnp.broadcast_to(jnp.arange(nb)[None, :, None], idx.shape)
    fog_sum = jnp.zeros((n_fog, nb, block), jnp.float32)
    return fog_sum.at[ff, bb, idx].add(contrib)


def compress_aggregate_wire_ref(
    delta: jax.Array,        # (N, nb, block)
    err: jax.Array,          # (N, nb, block)
    fog_id: jax.Array,       # (N,) int32
    weights: jax.Array,      # (N,) f32
    n_fog: int,
    k_per_block: int,
    quantize: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Sparse-wire twin of :func:`compress_aggregate_ref`.

    Emits the wire then consumes it with the scatter-accumulate; equal to
    the dense one-hot-GEMM oracle up to f32 summation order (scatter-add vs
    GEMM reduce) and threshold ties, which is why the chunked round path
    that uses it is pinned to tolerance rather than bitwise.
    """
    idx, q, scale, new_err = compress_wire_ref(delta, err, k_per_block, quantize)
    fog_sum = wire_aggregate_ref(
        idx, q, scale, fog_id, weights, n_fog, delta.shape[-1]
    )
    return fog_sum, new_err


def robust_aggregate_ref(
    recon: jax.Array,        # (N, d) per-client reconstructions
    fog_id: jax.Array,       # (N,) int32 cluster id per client
    weights: jax.Array,      # (N,) f32, zeroed for non-participants
    n_fog: int,
    trim_frac: float | jax.Array = 0.1,
    mode: str = "trimmed",
) -> tuple[jax.Array, jax.Array]:
    """Oracle for coordinate-wise Byzantine-robust fog aggregation.

    ``mode="trimmed"``: weighted trimmed mean — per fog and coordinate,
    the members' values are (conceptually) laid out on a weight axis of
    total mass W, the outer ``trim_frac`` mass is cut from EACH end, and
    the surviving mass is averaged.  Implemented sort-free via tie-group
    interval overlap: member i with value v_i owns the weight interval
    [A_i, A_i + g_i) scaled by w_i/g_i, where A_i is the weight strictly
    below v_i and g_i the weight tied at v_i; its surviving (effective)
    weight is the overlap of that interval with [beta W, (1 - beta) W].
    Order-independent, no data-dependent gathers, and at
    ``trim_frac == 0`` the overlap is exactly g_i — so the result reduces
    to the plain weighted mean bit-for-bit up to summation order (the
    equivalence pin in the tests).

    ``mode="median"``: weighted (lower) median — the tie group whose
    interval contains W/2.

    Returns (fog_out (n_fog, d) f32 — the NORMALISED robust aggregate per
    fog, zeros for empty fogs — and fog_weight (n_fog,) = sum of member
    weights, the Eq. 16 gateway weights).  ``trim_frac`` may be traced
    (config-axis sweeps); it is clamped below 0.5 — trimming half the
    mass from both ends leaves nothing.
    """
    v = recon.astype(jnp.float32)
    w_fog = jnp.where(
        fog_id[None, :] == jnp.arange(n_fog)[:, None],
        weights[None, :].astype(jnp.float32), 0.0,
    )                                                    # (M, N)
    fog_weight = jnp.sum(w_fog, axis=1)
    # Pairwise comparisons, shared across fogs: [i, k, d].
    less = (v[None, :, :] < v[:, None, :]).astype(jnp.float32)
    eq = (v[None, :, :] == v[:, None, :]).astype(jnp.float32)

    def one_fog(w):                                      # (N,) member weights
        big_w = jnp.sum(w)
        a = jnp.einsum("ikd,k->id", less, w)             # weight below v_i
        g = jnp.einsum("ikd,k->id", eq, w)               # weight tied at v_i
        g_safe = jnp.maximum(g, 1e-30)
        if mode == "median":
            half = 0.5 * big_w
            ratio = jnp.where((a < half) & (half <= a + g), 1.0 / g_safe, 0.0)
        else:
            beta = jnp.clip(jnp.asarray(trim_frac, jnp.float32), 0.0, 0.4995)
            lo = jnp.maximum(a, beta * big_w)
            hi = jnp.minimum(a + g, (1.0 - beta) * big_w)
            # overlap == g exactly at beta 0, so ratio == 1.0 exactly and
            # eff_i == w_i — the weighted-mean equivalence.
            ratio = jnp.maximum(hi - lo, 0.0) / g_safe
        eff = w[:, None] * ratio                         # (N, d)
        num = jnp.einsum("id,id->d", eff, v)
        den = jnp.sum(eff, axis=0)
        return num / jnp.maximum(den, 1e-12)

    return jax.vmap(one_fog)(w_fog), fog_weight


def fused_score_ref(
    x: jax.Array,                 # (R, d) telemetry rows
    ws: tuple[jax.Array, ...],    # per-layer weights, (d_in, d_out)
    bs: tuple[jax.Array, ...],    # per-layer biases, (d_out,)
    tau: jax.Array,               # (R,) per-row thresholds
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the fused anomaly-score kernel (serving hot path).

    AE forward (tanh hidden layers, linear output — exactly
    ``models/autoencoder.apply``), squared-L2 reconstruction error
    (Sec. V-D), and the Eq. 32 threshold compare in one computation.

    Returns (err (R,) f32, flag (R,) bool).  The dense reconstruction is
    an internal intermediate only — the fused kernel never writes it to
    HBM, and neither path returns it.
    """
    h = x.astype(jnp.float32)
    for i, (w, b) in enumerate(zip(ws, bs)):
        h = h @ w.astype(jnp.float32) + b.astype(jnp.float32)
        if i < len(ws) - 1:
            h = jnp.tanh(h)
    err = jnp.sum(jnp.square(x.astype(jnp.float32) - h), axis=-1)
    return err, err > tau


def fused_score_q8_ref(
    x: jax.Array,                  # (R, d) telemetry rows
    qws: tuple[jax.Array, ...],    # per-layer int8 weights, (d_in, d_out)
    sws: tuple[jax.Array, ...],    # per-layer scales, (1, d_out) f32
    bs: tuple[jax.Array, ...],     # per-layer f32 biases, (d_out,)
    tau: jax.Array,                # (R,) per-row thresholds
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the int8-weight fused score kernel: per-output-channel
    symmetric dequantisation (``w = q * scale``) INSIDE the program, then
    exactly :func:`fused_score_ref`.  The f32 weights never exist outside
    the compiled computation — the serving buffers stay int8."""
    ws = tuple(
        q.astype(jnp.float32) * s.astype(jnp.float32).reshape(1, -1)
        for q, s in zip(qws, sws)
    )
    return fused_score_ref(x, ws, bs, tau)


def local_train_ref(
    x: jax.Array,                 # (window, D) one client's resident window
    idx: jax.Array,               # (steps, bsz) int32 minibatch row indices
    ws: tuple[jax.Array, ...],    # per-layer weights, (d_in, d_out)
    bs: tuple[jax.Array, ...],    # per-layer biases, (d_out,)
    lr: float | jax.Array,
    mu: float | jax.Array = 0.0,
    use_prox: bool | None = None,
) -> tuple[tuple[jax.Array, ...], tuple[jax.Array, ...], jax.Array]:
    """Oracle for the fused local-training kernel (the client phase).

    Runs the whole E-epoch local solver of one client — exactly
    ``optim/sgd.local_sgd`` (``mu == 0``) / ``proximal_local_sgd``
    (``mu > 0``, FedProx with the broadcast params as anchor) over the
    ``models/autoencoder.loss`` objective — but assembles each minibatch by
    *indexing* the resident ``(window, D)`` data with ``idx`` instead of
    consuming a pre-gathered ``(steps, bsz, D)`` batch stream.  With
    ``idx = data/pipeline.multi_epoch_indices(key, ...)`` the two
    formulations see identical batches, so they agree to float tolerance.

    Returns (new_ws, new_bs, mean_loss).  ``lr``/``mu`` are traceable
    (pure arithmetic); ``use_prox`` is the STATIC proximal-term switch —
    None derives it from a concrete ``mu`` and defaults to True for a
    traced one (a runtime mu of 0 then contributes an exact zero term).
    """
    if use_prox is None:
        use_prox = not (isinstance(mu, (int, float)) and mu == 0.0)
    n_layers = len(ws)

    def loss_fn(params, batch):
        pw, pb = params
        h = batch
        for li in range(n_layers):
            h = h @ pw[li] + pb[li]
            if li < n_layers - 1:
                h = jnp.tanh(h)
        return jnp.mean(jnp.sum(jnp.square(batch - h), axis=-1))

    anchor = (ws, bs)
    grad_fn = jax.value_and_grad(loss_fn)

    def step(params, ib):
        loss, g = grad_fn(params, x[ib])
        if use_prox:
            g = jax.tree_util.tree_map(
                lambda gg, p, a: gg + mu * (p - a), g, params, anchor
            )
        new = jax.tree_util.tree_map(lambda p, gg: p - lr * gg, params, g)
        return new, loss

    (new_ws, new_bs), losses = jax.lax.scan(step, (ws, bs), idx)
    return new_ws, new_bs, jnp.mean(losses)


def sliding_window_decode_attention_ref(
    q: jax.Array,          # (Hq, d)
    k_cache: jax.Array,    # (S, Hkv, d)
    v_cache: jax.Array,    # (S, Hkv, d)
    cache_len: jax.Array,  # scalar int — number of valid cache entries
    window: int,           # attend to the last `window` positions
    scale: float | None = None,
) -> jax.Array:
    """One-token GQA decode attention over a sliding window. Returns (Hq, d)."""
    hq, d = q.shape
    s, hkv, _ = k_cache.shape
    g = hq // hkv
    if scale is None:
        scale = d ** -0.5
    qg = q.reshape(hkv, g, d).astype(jnp.float32)
    kf = k_cache.astype(jnp.float32)
    scores = jnp.einsum("hgd,shd->hgs", qg, kf) * scale     # (hkv, g, s)
    pos = jnp.arange(s)
    valid = (pos < cache_len) & (pos >= cache_len - window)
    scores = jnp.where(valid[None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hgs,shd->hgd", p, v_cache.astype(jnp.float32))
    return out.reshape(hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Anomaly Transformer (models/anomaly_transformer): plain float32 reference.
# ---------------------------------------------------------------------------

def _at_layer_norm_ref(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * g + b


def anomaly_transformer_window_ref(params, x, n_heads: int):
    """One window x (L, D) through the Anomaly Transformer, written out per
    head in float32: returns (x_hat (L, D), [S (H, L, L)], [P (H, L, L)],
    AssDis (L,)), where AssDis is the mean over layers and heads of
    KL(P || S) + KL(S || P) with 1e-4 in the logs."""
    import math

    length, _ = x.shape
    w = params["embed"]
    dm = w.shape[-1]
    h = jnp.zeros((length, dm))
    for t in range(length):
        for i in range(3):
            h = h.at[t].add(x[(t + i - 1) % length] @ w[i])
    pos = jnp.arange(length, dtype=jnp.float32)
    for c in range(dm):
        freq = math.exp(-(c - c % 2) * math.log(10000.0) / dm)
        h = h.at[:, c].add(jnp.sin(pos * freq) if c % 2 == 0 else jnp.cos(pos * freq))
    e = dm // n_heads
    dist = jnp.abs(pos[:, None] - pos[None, :])
    series_all, prior_all = [], []
    n_layers = params["layers"]["wq"].shape[0]
    for i in range(n_layers):
        lp = {name: leaf[i] for name, leaf in params["layers"].items()}
        outs, series, priors = [], [], []
        sig_all = h @ lp["ws"] + lp["bs"]
        for hd in range(n_heads):
            cols = slice(hd * e, (hd + 1) * e)
            q = h @ lp["wq"][:, cols] + lp["bq"][cols]
            k = h @ lp["wk"][:, cols] + lp["bk"][cols]
            v = h @ lp["wv"][:, cols] + lp["bv"][cols]
            s = jax.nn.softmax(q @ k.T / math.sqrt(e), axis=-1)
            sig = 3.0 ** (jax.nn.sigmoid(5.0 * sig_all[:, hd]) + 1e-5) - 1.0
            p = (1.0 / (math.sqrt(2.0 * math.pi) * sig[:, None])
                 * jnp.exp(-dist ** 2 / 2.0 / sig[:, None] ** 2))
            p = p / jnp.sum(p, axis=-1, keepdims=True)
            outs.append(s @ v)
            series.append(s)
            priors.append(p)
        a = jnp.concatenate(outs, axis=-1) @ lp["wo"] + lp["bo"]
        h = _at_layer_norm_ref(h + a, lp["ln1_g"], lp["ln1_b"])
        y = jax.nn.gelu(h @ lp["w1"] + lp["b1"], approximate=False) @ lp["w2"] + lp["b2"]
        h = _at_layer_norm_ref(h + y, lp["ln2_g"], lp["ln2_b"])
        series_all.append(jnp.stack(series))
        prior_all.append(jnp.stack(priors))
    h = _at_layer_norm_ref(h, params["norm_g"], params["norm_b"])
    x_hat = h @ params["proj_w"] + params["proj_b"]
    assdis = jnp.mean(jnp.stack([
        _at_kl_ref(p, s) + _at_kl_ref(s, p) for s, p in zip(series_all, prior_all)
    ]), axis=0)
    return x_hat, series_all, prior_all, assdis


def _at_kl_ref(p, q):
    """KL over keys with 1e-4 in the logs, mean over heads: (H, L, L) -> (L,)."""
    return jnp.mean(jnp.sum(p * (jnp.log(p + 1e-4) - jnp.log(q + 1e-4)), axis=-1), axis=0)


def anomaly_transformer_grads_ref(params, batch, n_heads: int, k: float):
    """The released code's minimax step on a batch (B, L, D): the gradient
    of rec - k AssDis(sg P, S) plus, separately taken, the gradient of
    rec + k AssDis(P, sg S).  Returns (gradient sum, rec - k AssDis)."""
    sg = jax.lax.stop_gradient

    def phase(p, sign):
        rec, dis = 0.0, 0.0
        for x in batch:
            x_hat, series, priors, _ = anomaly_transformer_window_ref(p, x, n_heads)
            rec = rec + jnp.mean((x_hat - x) ** 2)
            if sign < 0:
                pairs = [(s, sg(pr)) for s, pr in zip(series, priors)]
            else:
                pairs = [(sg(s), pr) for s, pr in zip(series, priors)]
            dis = dis + jnp.mean(jnp.stack([
                jnp.mean(_at_kl_ref(s, pr) + _at_kl_ref(pr, s)) for s, pr in pairs]))
        n = len(batch)
        return (rec + sign * k * dis) / n, (rec - k * dis) / n

    g1, loss1 = jax.grad(lambda p: phase(p, -1.0), has_aux=True)(params)
    g2 = jax.grad(lambda p: phase(p, 1.0)[0])(params)
    return jax.tree_util.tree_map(lambda a, b: a + b, g1, g2), loss1
