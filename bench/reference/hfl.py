"""Plain reference of one hierarchical federated training job.

A straightforward ``jax.numpy`` implementation of the paper's Algorithm 1
(arXiv 2603.24648) with selective fog cooperation, written from the
paper's equations and imported from nothing in ``src/``: a 3-D stratified
deployment with Gauss-Markov fog drift, the Thorp/Wenz acoustic channel
with a capped source level, nearest-feasible-fog association, the
selective cooperation rule (Eqs. 28-29), E epochs of local SGD on the
32-16-8-16-32 autoencoder, error-feedback Top-K with int8 quantisation
(Eq. 30), fog and gateway aggregation (Eqs. 13, 15, 16), the energy terms
(Eqs. 17-20) and the 99th-percentile detector evaluation.

It draws its randomness with the same ``jax.random`` calls in the same
order as the system under test, so one trial key gives the same
deployment, the same initial weights and the same minibatches: the
comparison then measures arithmetic alone.  Two departures from the
kernels are deliberate: the Top-K set is the exact K largest magnitudes
(a sort), where the kernels bisect for the threshold, and nothing is
padded or blocked.

``dtype`` is the precision of the model arithmetic (local training and
the detector's forward pass); the physics and the aggregation stay in
float32.  The benchmark computes the reference in float32 under
``jax.default_matmul_precision("highest")``; the control of ``correct``
computes it in bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Acoustic channel and energy constants (paper Table II).
FREQ_KHZ, BANDWIDTH_HZ, SPREADING_K = 12.0, 4000.0, 1.5
WIND_M_S, SHIPPING = 5.0, 0.5
GAMMA_TGT_DB, IMPL_LOSS_DB, SL_MAX_DB = 10.0, 2.0, 140.0
SOUND_SPEED, P_REF, RHO_WATER = 1500.0, 1e-6, 1025.0
ETA_EA, P_CIRCUIT_TX, E_INIT_J, E_MIN_J, EPS_OP_J = 0.25, 0.05, 500.0, 0.0, 1e-9
# Geometry (Table II) and fog mobility.
LX, LY = 2000.0, 2000.0
SENSOR_DEPTH, FOG_DEPTH = (500.0, 1000.0), (100.0, 400.0)
FOG_SPEED, GM_ALPHA, ROUND_S = 0.5, 0.75, 60.0
# Selective cooperation (Eqs. 28-29).
ELIGIBILITY, W_SELF, W_PEER = 0.75, 0.8, 0.2


def ae_dims(cfg: dict) -> tuple[int, ...]:
    d = cfg["feature_dim"]
    return (d, *cfg["hidden"], d)


def ae_init(key, dims):
    """Glorot-normal weights, zero biases: one (w, b) pair per layer."""
    keys = jax.random.split(key, len(dims) - 1)
    return [
        (math.sqrt(2.0 / (a + b)) * jax.random.normal(k, (a, b)),
         jnp.zeros((b,)))
        for k, (a, b) in zip(keys, zip(dims[:-1], dims[1:]))
    ]


def ae_apply(params, x):
    h = x
    for i, (w, b) in enumerate(params):
        h = h @ w + b
        if i < len(params) - 1:
            h = jnp.tanh(h)
    return h


def ae_error(params, x):
    """Squared-L2 reconstruction error per row."""
    return jnp.sum(jnp.square(x - ae_apply(params, x)), axis=-1)


def _cast(params, dtype):
    return [(w.astype(dtype), b.astype(dtype)) for w, b in params]


# --- channel and energy ----------------------------------------------------

def _log10(x):
    return jnp.log10(jnp.asarray(x, jnp.float32))


def noise_level_db():
    f = jnp.float32(FREQ_KHZ)
    lf = _log10(f)
    parts = jnp.stack([
        17.0 - 30.0 * lf,
        40.0 + 20.0 * (SHIPPING - 0.5) + 26.0 * lf - 60.0 * _log10(f + 0.03),
        50.0 + 7.5 * math.sqrt(WIND_M_S) + 20.0 * lf - 40.0 * _log10(f + 0.4),
        -15.0 + 20.0 * lf,
    ])
    n0 = 10.0 * jnp.log10(jnp.sum(10.0 ** (parts / 10.0)))
    return n0 + 10.0 * _log10(BANDWIDTH_HZ)


def min_source_level_db(dist):
    d = jnp.maximum(jnp.asarray(dist, jnp.float32), 1.0)
    f2 = jnp.float32(FREQ_KHZ) ** 2
    alpha = 0.11 * f2 / (1 + f2) + 44.0 * f2 / (4100.0 + f2) + 2.75e-4 * f2 + 0.003
    tl = 10.0 * SPREADING_K * jnp.log10(d) + alpha * d / 1000.0
    return GAMMA_TGT_DB + tl + noise_level_db() + IMPL_LOSS_DB


def feasible(dist):
    return min_source_level_db(dist) <= SL_MAX_DB


def tx_energy_j(bits, dist):
    sl = min_source_level_db(dist)
    p_ac = 4.0 * jnp.pi * P_REF**2 / (RHO_WATER * SOUND_SPEED) * 10.0 ** (sl / 10.0)
    rate = BANDWIDTH_HZ * jnp.log2(1.0 + 10.0 ** (GAMMA_TGT_DB / 10.0))
    e = (p_ac / ETA_EA + P_CIRCUIT_TX) * bits / rate
    return jnp.where(sl <= SL_MAX_DB, e, jnp.inf)


def distances(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a[:, None, :] - b[None, :, :]), -1) + 1e-12)


# --- deployment, association, cooperation ---------------------------------

def _stratum(key, n, depth):
    kx, ky, kz = jax.random.split(key, 3)
    return jnp.stack([
        jax.random.uniform(kx, (n,), minval=0.0, maxval=LX),
        jax.random.uniform(ky, (n,), minval=0.0, maxval=LY),
        jax.random.uniform(kz, (n,), minval=depth[0], maxval=depth[1]),
    ], axis=-1)


def fog_drift(key, pos, vel):
    noise = jax.random.normal(key, vel.shape) * FOG_SPEED
    vel = GM_ALPHA * vel + math.sqrt(1.0 - GM_ALPHA**2) * noise
    pos = pos + vel * ROUND_S
    lo = jnp.array([0.0, 0.0, FOG_DEPTH[0]], jnp.float32)
    hi = jnp.array([LX, LY, FOG_DEPTH[1]], jnp.float32)
    over, under = pos > hi, pos < lo
    pos = jnp.where(over, 2.0 * hi - pos, pos)
    pos = jnp.where(under, 2.0 * lo - pos, pos)
    return jnp.clip(pos, lo, hi), jnp.where(over | under, -vel, vel)


def associate(sensor_pos, fog_pos):
    """Nearest feasible fog per sensor: (fog id, has a feasible fog, distance)."""
    d = distances(sensor_pos, fog_pos)
    ok = feasible(d)
    fog = jnp.argmin(jnp.where(ok, d, jnp.inf), axis=-1)
    dist = jnp.take_along_axis(d, fog[:, None], axis=-1)[:, 0]
    return fog, jnp.any(ok, axis=-1), dist


def selective(fog_pos, size):
    """Eqs. 28-29: (partner, self weight, peer weight, cooperates, distance)."""
    m = fog_pos.shape[0]
    d = distances(fog_pos, fog_pos) + jnp.diag(jnp.full((m,), jnp.inf))
    ok = feasible(d)
    c = size.astype(jnp.float32)
    nonempty = c > 0
    mean_c = jnp.sum(c * nonempty) / jnp.maximum(jnp.sum(nonempty), 1.0)
    eligible = c <= jnp.maximum(2.0, ELIGIBILITY * mean_c)
    q1 = jnp.nanquantile(
        jnp.where(jnp.any(ok), jnp.where(ok, d, jnp.nan), 0.0), 0.25)
    cand = ok & (c[None, :] > c[:, None]) & nonempty[None, :] & (d < q1)
    partner = jnp.argmin(jnp.where(cand, d, jnp.inf), axis=-1)
    coop = eligible & jnp.any(cand, axis=-1) & nonempty
    idx = jnp.arange(m)
    pdist = jnp.take_along_axis(d, partner[:, None], axis=-1)[:, 0]
    return (jnp.where(coop, partner, idx), jnp.where(coop, W_SELF, 1.0),
            jnp.where(coop, W_PEER, 0.0), coop, jnp.where(coop, pdist, 0.0))


# --- clients ---------------------------------------------------------------

def local_sgd(params, window, key, cfg, dtype):
    """E epochs of minibatch SGD on one client's window; returns the update
    of every leaf (float32) and the mean minibatch loss."""
    n, bs, epochs = window.shape[0], cfg["batch_size"], cfg["local_epochs"]
    nb = n // bs
    perms = jax.vmap(lambda k: jax.random.permutation(k, n)[: nb * bs])(
        jax.random.split(key, epochs))
    idx = perms.reshape(epochs * nb, bs)
    x = window.astype(dtype)
    p0 = _cast(params, dtype)
    lr = jnp.asarray(cfg["lr"], dtype)

    def loss(p, batch):
        return jnp.mean(jnp.sum(jnp.square(batch - ae_apply(p, batch)), -1))

    def step(p, ib):
        value, g = jax.value_and_grad(loss)(p, x[ib])
        return [(w - lr * gw, b - lr * gb) for (w, b), (gw, gb) in zip(p, g)], value

    p1, losses = jax.lax.scan(step, p0, idx)
    delta = [((w1 - w0).astype(jnp.float32), (b1 - b0).astype(jnp.float32))
             for (w1, b1), (w0, b0) in zip(p1, p0)]
    return delta, jnp.mean(losses.astype(jnp.float32))


def flatten(params):
    """One client's update as a vector (leaf order is immaterial to a
    global Top-K and a global quantisation scale)."""
    return jnp.concatenate([jnp.concatenate([w.reshape(-1), b]) for w, b in params])


def unflatten(vec, dims):
    out, at = [], 0
    for a, b in zip(dims[:-1], dims[1:]):
        w = vec[at: at + a * b].reshape(a, b)
        at += a * b
        out.append((w, vec[at: at + b]))
        at += b
    return out


def compress(v, k):
    """Eq. 30 on one client: keep the k largest magnitudes of v, int8 with
    one symmetric scale (max |v| / 127); returns (what the fog decodes,
    the new error-feedback buffer)."""
    mag = jnp.abs(v)
    kth = jnp.sort(mag)[-k]
    kept = jnp.where(mag >= kth, v, 0.0)
    scale = jnp.max(mag) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    recon = jnp.where(scale > 0, jnp.clip(jnp.round(kept / safe), -127, 127) * scale, 0.0)
    return recon, v - recon


def payload_bits(d, k, quant_bits):
    return k * (quant_bits + math.ceil(math.log2(max(d, 2))))


# --- one trial and one job ---------------------------------------------------

def trial(key, ds, cfg, dtype=jnp.float32):
    """Train and evaluate one (seed, deployment) trial from its key."""
    dims = ae_dims(cfg)
    n, n_fog = cfg["n_sensors"], cfg["n_fog"]
    k_init, k_train = jax.random.split(key)
    params = ae_init(k_init, dims)
    kd, key = jax.random.split(k_train)
    ks, kf = jax.random.split(kd)
    sensor_pos = _stratum(ks, n, SENSOR_DEPTH)
    fog_pos = _stratum(kf, n_fog, FOG_DEPTH)
    gateway = jnp.array([LX / 2.0, LY / 2.0, 0.0], jnp.float32)
    d = sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    k = max(1, round(cfg["rho_s"] * d))
    l_up = float(payload_bits(d, k, cfg["quant_bits"]))
    l_full = 32.0 * d
    flops = 3 * sum(2 * a * b for a, b in zip(dims[:-1], dims[1:])) \
        * ds["train"].shape[1] * cfg["local_epochs"]
    weights_n = jnp.full((n,), float(ds["train"].shape[1]))

    def round_fn(carry, _):
        params, err, battery, fog_pos, fog_vel, key = carry
        key, k_mob, k_clients = jax.random.split(key, 3)
        fog_pos, fog_vel = fog_drift(k_mob, fog_pos, fog_vel)
        fog, reach, dist = associate(sensor_pos, fog_pos)
        active = reach & (battery > E_MIN_J)
        size = jax.ops.segment_sum(active.astype(jnp.int32), fog, num_segments=n_fog)
        partner, w_self, w_peer, coop, pdist = selective(fog_pos, size)

        deltas, losses = jax.vmap(
            lambda win, kk: local_sgd(params, win, kk, cfg, dtype)
        )(ds["train"], jax.random.split(k_clients, n))
        flat = jax.vmap(flatten)(deltas)
        recon, new_err = jax.vmap(lambda v: compress(v, k))(flat + err)
        w = weights_n * active
        fog_w = jax.ops.segment_sum(w, fog, num_segments=n_fog)
        fog_sum = jax.ops.segment_sum(recon * w[:, None], fog, num_segments=n_fog)
        g0 = flatten(params)
        fog_model = fog_sum / jnp.maximum(fog_w, 1e-12)[:, None] + g0[None, :]
        mixed = w_self[:, None] * fog_model + w_peer[:, None] * fog_model[partner]
        total = jnp.sum(fog_w)
        g1 = jnp.where(total > 0, (fog_w / jnp.maximum(total, 1e-12)) @ mixed, g0)
        err = jnp.where(active[:, None], new_err, err)

        fog_on = fog_w > 0
        fog_gw = jnp.linalg.norm(fog_pos - gateway[None, :], axis=-1)
        gw_ok = feasible(fog_gw)
        e_up = jnp.where(active, tx_energy_j(l_up, dist), 0.0)
        e_total = (jnp.sum(e_up)
                   + jnp.sum(jnp.where(coop & fog_on, tx_energy_j(l_full, pdist), 0.0))
                   + jnp.sum(jnp.where(fog_on & gw_ok, tx_energy_j(l_full, fog_gw), 0.0)))
        spent = e_up + jnp.where(active, EPS_OP_J * flops, 0.0)
        battery = jnp.maximum(battery - spent, E_MIN_J)
        a = active.astype(jnp.float32)
        out = dict(loss=jnp.sum(losses * a) / jnp.maximum(jnp.sum(a), 1.0),
                   e_total=e_total, participation=jnp.mean(a))
        return (unflatten(g1, dims), err, battery, fog_pos, fog_vel, key), out

    carry0 = (params, jnp.zeros((n, d)), jnp.full((n,), E_INIT_J), fog_pos,
              jnp.zeros((n_fog, 3)), key)
    (final, *_), m = jax.lax.scan(round_fn, carry0, None, length=cfg["rounds"])

    # Detector: 99th-percentile threshold on normal validation rows (Eq. 32).
    dim = cfg["feature_dim"]
    model = _cast(final, dtype)
    val_err = ae_error(model, ds["val"].reshape(-1, dim).astype(dtype))
    tau = jnp.percentile(val_err.astype(jnp.float32), cfg["percentile"])
    test_err = ae_error(model, ds["test"].reshape(-1, dim).astype(dtype))
    pred = (test_err.astype(jnp.float32) > tau).astype(jnp.float32)
    label = ds["test_label"].reshape(-1).astype(jnp.float32)
    tp = jnp.sum(pred * label)
    prec = tp / jnp.maximum(jnp.sum(pred), 1e-12)
    rec = tp / jnp.maximum(jnp.sum(label), 1e-12)
    f1 = 2 * prec * rec / jnp.maximum(prec + rec, 1e-12)
    return dict(losses=m["loss"], e_total=jnp.sum(m["e_total"]),
                participation=jnp.mean(m["participation"]), f1=f1,
                init=params, final=final)


def job(keys, ds, cfg, dtype=jnp.float32):
    """Every trial of one job: ``keys`` is the (S,) array of trial keys,
    ``ds`` a dict of the dataset's arrays without a seed axis."""
    return jax.vmap(lambda k: trial(k, ds, cfg, dtype))(keys)
