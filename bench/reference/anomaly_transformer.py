"""Plain reference of one hierarchical federated training job of the
Anomaly Transformer (arXiv 2110.02642) on the paper's deployment (arXiv
2603.24648 Table II).

Written from the two papers' equations and the Anomaly Transformer's
released training step, in plain float32 ``jax.numpy``, and imported from
nothing in ``src/``.  The physics (deployment, fog drift, channel,
association, selective cooperation, energy, the uplink payload) are the
functions of ``bench/reference/hfl.py``, imported unedited.

The model is written for one window (L, D); a minibatch of windows runs
through the same code by broadcasting over a leading axis.  Clients train
one after another in a ``lax.scan`` (no vmap, no chunks), each with E
epochs of minibatch SGD on its stride-1 windows: the window starts are
shuffled per epoch and the remainder dropped.  One SGD
step takes the released code's two gradients separately and sums them:
that of rec - k AssDis(S, sg P) and that of rec + k AssDis(P, sg S), with
rec the mean squared error and AssDis the mean over layers and heads of
KL(P || S) + KL(S || P) (1e-4 in the logs).  Each client's update goes
through error-feedback Top-K with int8 quantisation in blocks of 8,192
coordinates (the exact K largest magnitudes of each block by a sort, one
scale per block), the fog sums, cooperative mixing and gateway average.
Evaluation scores the validation and test series in non-overlapping
windows: softmax over a window's positions of -50 AssDis_i, times the
position's squared error (mean over features); tau is the 99th
percentile of the validation scores, F1 is point-wise.

It draws its randomness with the same ``jax.random`` calls in the same
order as the system under test, so one trial key gives the same
deployment, initial weights and minibatches.  Each sensor's battery
starts at the configuration's ``e_init_j``; local compute is charged for
the windows a sensor trains.  ``dtype`` is the precision
of the model arithmetic; physics and aggregation stay in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import hfl as phys

BLOCK = 8192
KL_EPS = 1e-4


# --- model -----------------------------------------------------------------

def _glorot(key, shape, fan_in, fan_out):
    return math.sqrt(2.0 / (fan_in + fan_out)) * jax.random.normal(key, shape)


def init(key, dim, cfg):
    """Glorot-normal weights, zero biases, unit LayerNorm gains; the keys
    split as the system splits them, each layer's leaves stacked along a
    leading layer axis."""
    dm, h, ff = cfg["d_model"], cfg["n_heads"], cfg["d_ff"]
    k_embed, k_proj, *k_layers = jax.random.split(key, 2 + cfg["e_layers"])
    layers = []
    for kl in k_layers:
        kq, kk, kv, ko, ks, k1, k2 = jax.random.split(kl, 7)
        layers.append({
            "wq": _glorot(kq, (dm, dm), dm, dm), "bq": jnp.zeros((dm,)),
            "wk": _glorot(kk, (dm, dm), dm, dm), "bk": jnp.zeros((dm,)),
            "wv": _glorot(kv, (dm, dm), dm, dm), "bv": jnp.zeros((dm,)),
            "wo": _glorot(ko, (dm, dm), dm, dm), "bo": jnp.zeros((dm,)),
            "ws": _glorot(ks, (dm, h), dm, h), "bs": jnp.zeros((h,)),
            "ln1_g": jnp.ones((dm,)), "ln1_b": jnp.zeros((dm,)),
            "w1": _glorot(k1, (dm, ff), dm, ff), "b1": jnp.zeros((ff,)),
            "w2": _glorot(k2, (ff, dm), ff, dm), "b2": jnp.zeros((dm,)),
            "ln2_g": jnp.ones((dm,)), "ln2_b": jnp.zeros((dm,)),
        })
    return {
        "embed": _glorot(k_embed, (3, dim, dm), 3 * dim, dm),
        "layers": {name: jnp.stack([lp[name] for lp in layers]) for name in layers[0]},
        "norm_g": jnp.ones((dm,)), "norm_b": jnp.zeros((dm,)),
        "proj_w": _glorot(k_proj, (dm, dim), dm, dim),
        "proj_b": jnp.zeros((dim,)),
    }


def _norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * g + b


def forward(p, x, n_heads):
    """x (..., L, D) -> (x_hat, S, P); S and P are (layers, ..., H, L, L)."""
    length = x.shape[-2]
    dm = p["embed"].shape[-1]
    # Circular Conv1d, kernel 3: tap 0 reads t-1, tap 1 reads t, tap 2 t+1.
    h = (jnp.roll(x, 1, axis=-2) @ p["embed"][0] + x @ p["embed"][1]
         + jnp.roll(x, -1, axis=-2) @ p["embed"][2])
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dm, 2, dtype=jnp.float32) * -(math.log(10000.0) / dm))
    pe = jnp.stack([jnp.sin(pos * div), jnp.cos(pos * div)], axis=-1).reshape(length, dm)
    h = h + pe.astype(h.dtype)
    e = dm // n_heads
    idx = jnp.arange(length, dtype=jnp.float32)
    dist = jnp.abs(idx[:, None] - idx[None, :])

    def heads(a):   # (..., L, H * E) -> (..., H, L, E)
        return jnp.swapaxes(a.reshape(*a.shape[:-1], n_heads, e), -2, -3)

    def layer(h, lp):
        q = heads(h @ lp["wq"] + lp["bq"])
        k = heads(h @ lp["wk"] + lp["bk"])
        v = heads(h @ lp["wv"] + lp["bv"])
        s = jax.nn.softmax(q @ jnp.swapaxes(k, -1, -2) / math.sqrt(e), axis=-1)
        sig = jnp.swapaxes(h @ lp["ws"] + lp["bs"], -1, -2)          # (..., H, L)
        sig = (3.0 ** (jax.nn.sigmoid(5.0 * sig) + 1e-5) - 1.0)[..., None]
        pr = 1.0 / (math.sqrt(2.0 * math.pi) * sig) * jnp.exp(-dist ** 2 / 2.0 / sig ** 2)
        pr = pr / jnp.sum(pr, axis=-1, keepdims=True)
        o = jnp.swapaxes(s @ v, -2, -3)
        h = _norm(h + o.reshape(*o.shape[:-2], dm) @ lp["wo"] + lp["bo"],
                  lp["ln1_g"], lp["ln1_b"])
        y = jax.nn.gelu(h @ lp["w1"] + lp["b1"], approximate=False) @ lp["w2"] + lp["b2"]
        return _norm(h + y, lp["ln2_g"], lp["ln2_b"]), (s, pr)

    h, (all_s, all_p) = jax.lax.scan(layer, h, p["layers"])
    h = _norm(h, p["norm_g"], p["norm_b"])
    return h @ p["proj_w"] + p["proj_b"], all_s, all_p


def kl(a, b):
    """Sum over keys of a (log(a + 1e-4) - log(b + 1e-4)), per query."""
    return jnp.sum(a * (jnp.log(a + KL_EPS) - jnp.log(b + KL_EPS)), axis=-1)


def discrepancy(all_s, all_p, stop_s=False, stop_p=False):
    """AssDis per position (..., L): mean over layers and heads of
    KL(P || S) + KL(S || P), with S or P held constant where asked."""
    sg = jax.lax.stop_gradient
    s = sg(all_s) if stop_s else all_s
    pr = sg(all_p) if stop_p else all_p
    return jnp.mean(jnp.mean(kl(pr, s) + kl(s, pr), axis=-2), axis=0)


def minimax_grads(p, batch, cfg):
    """The released code's step: two gradients taken separately, summed.
    Returns (gradient, rec - k AssDis, AssDis)."""
    k = cfg["k"]

    def phase(params, sign):
        x_hat, all_s, all_p = forward(params, batch, cfg["n_heads"])
        rec = jnp.mean((x_hat - batch) ** 2)
        if sign < 0:
            dis = jnp.mean(discrepancy(all_s, all_p, stop_p=True))
        else:
            dis = jnp.mean(discrepancy(all_s, all_p, stop_s=True))
        return rec + sign * k * dis, (rec, dis)

    g1, (rec, dis) = jax.grad(lambda q: phase(q, -1.0), has_aux=True)(p)
    g2, _ = jax.grad(lambda q: phase(q, 1.0), has_aux=True)(p)
    grad = jax.tree_util.tree_map(lambda a, b: a + b, g1, g2)
    return grad, rec - k * dis, dis


def score(p, windows, n_heads):
    """(n, L, D) windows -> (n, L) anomaly scores."""
    x_hat, all_s, all_p = forward(p, windows, n_heads)
    err = jnp.mean((windows - x_hat) ** 2, axis=-1)
    return jax.nn.softmax(-50.0 * discrepancy(all_s, all_p), axis=-1) * err


def forward_flops(cfg):
    """Multiply-adds of one window's forward, two operations each."""
    n, dim, dm, h, ff = (cfg["win_size"], cfg["feature_dim"], cfg["d_model"],
                         cfg["n_heads"], cfg["d_ff"])
    per_layer = 4 * n * dm * dm + n * dm * h + 2 * n * n * dm + 2 * n * dm * ff
    return 2 * (n * 3 * dim * dm + cfg["e_layers"] * per_layer + n * dm * dim)


# --- clients ---------------------------------------------------------------

def flatten(p):
    return jnp.concatenate([leaf.reshape(-1) for leaf in jax.tree_util.tree_leaves(p)])


def unflatten(vec, like):
    leaves, tree = jax.tree_util.tree_flatten(like)
    out, at = [], 0
    for leaf in leaves:
        out.append(vec[at: at + leaf.size].reshape(leaf.shape))
        at += leaf.size
    return jax.tree_util.tree_unflatten(tree, out)


def local_sgd(params, series, key, cfg, dtype):
    """E epochs of minibatch SGD on one client's stride-1 windows; returns
    the float32 update, the mean minibatch loss and mean AssDis."""
    length, bs, epochs = cfg["win_size"], cfg["batch_size"], cfg["local_epochs"]
    n = series.shape[0] - length + 1
    nb = n // bs
    perms = [jax.random.permutation(k, n)[: nb * bs] for k in jax.random.split(key, epochs)]
    starts = jnp.stack(perms).reshape(epochs * nb, bs)
    x = series.astype(dtype)
    p0 = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    lr = jnp.asarray(cfg["lr"], dtype)

    def step(p, st):
        batch = x[st[:, None] + jnp.arange(length)[None, :]]
        g, loss, dis = minimax_grads(p, batch, cfg)
        return jax.tree_util.tree_map(lambda w, gw: w - lr * gw, p, g), (loss, dis)

    p1, (losses, dis) = jax.lax.scan(step, p0, starts)
    delta = jax.tree_util.tree_map(lambda a, b: (a - b).astype(jnp.float32), p1, p0)
    return delta, jnp.mean(losses.astype(jnp.float32)), jnp.mean(dis.astype(jnp.float32))


def block_keep(d, rho_s):
    """Coordinates kept in each block of 8,192: the uniform count that keeps
    about rho_s d in all, the last (partial) block holding at most its own
    coordinates."""
    nb = -(-d // BLOCK)
    tail = d - (nb - 1) * BLOCK
    target = max(1, round(rho_s * d))
    k = target / nb
    if nb > 1 and k > tail:
        k = (target - tail) / (nb - 1)
    return min(BLOCK, max(1, round(k))), nb


def compress_blocks(v, k, nb):
    """Eq. 30 per block of 8,192: keep the k largest magnitudes, int8 with
    one symmetric scale per block (block max / 127); returns (what the fog
    decodes, the new error-feedback buffer)."""
    d = v.shape[0]
    blocks = jnp.zeros((nb * BLOCK,)).at[:d].set(v).reshape(nb, BLOCK)
    mag = jnp.abs(blocks)
    kth = jnp.sort(mag, axis=-1)[:, -k][:, None]
    kept = jnp.where(mag >= kth, blocks, 0.0)
    scale = jnp.max(mag, axis=-1, keepdims=True) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    recon = jnp.where(scale > 0, jnp.clip(jnp.round(kept / safe), -127, 127) * scale, 0.0)
    recon = recon.reshape(-1)[:d]
    return recon, v - recon


# --- one trial ---------------------------------------------------------------

def trial(key, ds, cfg, dtype=jnp.float32):
    """Train and evaluate one (seed, deployment) trial from its key."""
    n, n_fog, length = cfg["n_sensors"], cfg["n_fog"], cfg["win_size"]
    k_init, k_train = jax.random.split(key)
    params = init(k_init, cfg["feature_dim"], cfg)
    kd, key = jax.random.split(k_train)
    ks, kf = jax.random.split(kd)
    sensor_pos = phys._stratum(ks, n, phys.SENSOR_DEPTH)
    fog_pos = phys._stratum(kf, n_fog, phys.FOG_DEPTH)
    gateway = jnp.array([phys.LX / 2.0, phys.LY / 2.0, 0.0], jnp.float32)
    d = flatten(params).shape[0]
    k_blk, nb = block_keep(d, cfg["rho_s"])
    l_up = float(phys.payload_bits(d, max(1, round(cfg["rho_s"] * d)), cfg["quant_bits"]))
    l_full = 32.0 * d
    t_rows = ds["train"].shape[1]
    # compute is charged for the windows trained: whole minibatches an epoch
    bs = cfg["batch_size"]
    flops = 3 * forward_flops(cfg) * cfg["local_epochs"] * ((t_rows - length + 1) // bs) * bs
    weights_n = jnp.full((n,), float(t_rows))

    def round_fn(carry, _):
        params, err, battery, fog_pos, fog_vel, key = carry
        key, k_mob, k_clients = jax.random.split(key, 3)
        fog_pos, fog_vel = phys.fog_drift(k_mob, fog_pos, fog_vel)
        fog, reach, dist = phys.associate(sensor_pos, fog_pos)
        active = reach & (battery > phys.E_MIN_J)
        size = jax.ops.segment_sum(active.astype(jnp.int32), fog, num_segments=n_fog)
        partner, w_self, w_peer, coop, pdist = phys.selective(fog_pos, size)
        w = weights_n * active
        keys = jax.random.split(k_clients, n)

        def client(c, i):
            fog_sum, err = c
            delta, loss, dis = local_sgd(params, ds["train"][i], keys[i], cfg, dtype)
            recon, new_err = compress_blocks(flatten(delta) + err[i], k_blk, nb)
            fog_sum = fog_sum.at[fog[i]].add(w[i] * recon)
            err = err.at[i].set(jnp.where(active[i], new_err, err[i]))
            return (fog_sum, err), (loss, dis)

        (fog_sum, err), (losses, dis) = jax.lax.scan(
            client, (jnp.zeros((n_fog, d)), err), jnp.arange(n))
        fog_w = jax.ops.segment_sum(w, fog, num_segments=n_fog)
        g0 = flatten(params)
        fog_model = fog_sum / jnp.maximum(fog_w, 1e-12)[:, None] + g0[None, :]
        mixed = w_self[:, None] * fog_model + w_peer[:, None] * fog_model[partner]
        total = jnp.sum(fog_w)
        g1 = jnp.where(total > 0, (fog_w / jnp.maximum(total, 1e-12)) @ mixed, g0)

        fog_on = fog_w > 0
        fog_gw = jnp.linalg.norm(fog_pos - gateway[None, :], axis=-1)
        gw_ok = phys.feasible(fog_gw)
        e_up = jnp.where(active, phys.tx_energy_j(l_up, dist), 0.0)
        e_total = (jnp.sum(e_up)
                   + jnp.sum(jnp.where(coop & fog_on, phys.tx_energy_j(l_full, pdist), 0.0))
                   + jnp.sum(jnp.where(fog_on & gw_ok, phys.tx_energy_j(l_full, fog_gw), 0.0)))
        spent = e_up + jnp.where(active, phys.EPS_OP_J * flops, 0.0)
        battery = jnp.maximum(battery - spent, phys.E_MIN_J)
        a = active.astype(jnp.float32)
        count = jnp.maximum(jnp.sum(a), 1.0)
        out = dict(loss=jnp.sum(losses * a) / count, assdis=jnp.sum(dis * a) / count,
                   e_total=e_total, participation=jnp.mean(a))
        return (unflatten(g1, params), err, battery, fog_pos, fog_vel, key), out

    carry0 = (params, jnp.zeros((n, d)), jnp.full((n,), float(cfg["e_init_j"])), fog_pos,
              jnp.zeros((n_fog, 3)), key)
    (final, *_), m = jax.lax.scan(round_fn, carry0, None, length=cfg["rounds"])

    model = jax.tree_util.tree_map(lambda a: a.astype(dtype), final)

    def series_scores(x):
        n_win = x.shape[0] * (x.shape[1] // length)
        w = x[:, :n_win // x.shape[0] * length].astype(dtype)
        group = max(g for g in range(1, 65) if n_win % g == 0)
        w = w.reshape(n_win // group, group, length, x.shape[-1])
        return jax.lax.map(lambda blk: score(model, blk, cfg["n_heads"]), w)

    val = series_scores(ds["val"]).astype(jnp.float32).reshape(-1)
    test = series_scores(ds["test"]).astype(jnp.float32).reshape(-1)
    n_test = ds["test"].shape[1] // length * length
    label = ds["test_label"][:, :n_test].reshape(-1).astype(jnp.float32)
    tau = jnp.percentile(val, cfg["percentile"])
    pred = (test > tau).astype(jnp.float32)
    tp = jnp.sum(pred * label)
    prec = tp / jnp.maximum(jnp.sum(pred), 1e-12)
    rec = tp / jnp.maximum(jnp.sum(label), 1e-12)
    f1 = 2 * prec * rec / jnp.maximum(prec + rec, 1e-12)
    return dict(losses=m["loss"], assdis=m["assdis"], e_total=jnp.sum(m["e_total"]),
                participation=jnp.mean(m["participation"]), f1=f1,
                init=params, final=final)
