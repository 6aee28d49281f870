"""Flat (star-topology) FL baselines: FedAvg, FedProx, SCAFFOLD, and the
centralised oracle (paper Sec. VI-B).

Flat methods are participation-limited: only sensors with a feasible
*direct* sensor->gateway acoustic link upload updates (Sec. IV-E).  The
centralised oracle pools raw data at the gateway — underwater-infeasible,
kept as a reference; its energy is the raw-data upload cost through each
sensor's cheapest feasible path (direct if feasible, else the 2-hop
sensor->fog->gateway relay), which is the assumption that makes Table IV's
finite centralised energies reproducible.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import aggregation as agg
from repro.core import association as assoc
from repro.core import compression as comp
from repro.core import energy as en
from repro.core import faults as flt
from repro.core import topology as topo
from repro.core.hfl import (
    HFLConfig, HFLState, RoundMetrics, _client_train_fn, _clients_round,
)
from repro.kernels import ops as kops
from repro.data.pipeline import multi_epoch_batches
from repro.data.synthetic import SensorDataset
from repro.launch.mesh import shard_map_compat
from repro.models.detector import as_detector
from repro.optim import scaffold as scf
from repro.optim import server as srv
from repro.optim.sgd import local_sgd

Params = Any
LossFn = Callable[[Params, jax.Array], jax.Array]


def make_flat_round_fn(
    loss_fn: LossFn,
    ds: SensorDataset,
    cfg: HFLConfig,
    *,
    client_mesh: Mesh | None = None,
) -> Callable[[HFLState, None], tuple[HFLState, RoundMetrics]]:
    """FedAvg (prox_mu=0) / FedProx (prox_mu>0) direct-to-gateway round.

    The gateway is a single "cluster": local training runs through the
    same fused batched client solver as the hierarchical loop (see
    :func:`repro.optim.sgd.make_client_solver`; ``prox_mu > 0`` = FedProx
    in-kernel), and compression + the weighted FedAvg mean through the
    fused compress-and-aggregate operator, with ``n_fog=1``.
    ``client_mesh`` shards the client axis exactly as in
    :func:`repro.core.hfl.make_round_fn`.
    """
    clients_fn = _client_train_fn(loss_fn, cfg)
    if cfg.robust not in ("mean", "trimmed", "median"):
        raise ValueError(
            f"robust must be 'mean', 'trimmed' or 'median', got "
            f"{cfg.robust!r}"
        )
    fl = cfg.faults
    fault_on = fl.is_active       # STATIC: off => exact legacy round
    dr = cfg.drift
    drift_on = dr.is_active       # STATIC: off => exact legacy round
    adaptive = fault_on and fl.byz_mode == "adaptive"
    if client_mesh is not None and (fault_on or cfg.robust != "mean"):
        raise ValueError(
            "client-sharded rounds do not support fault injection or "
            "robust aggregation (the per-client reconstructions never "
            "leave their shard)"
        )
    if client_mesh is not None and drift_on:
        raise ValueError(
            "client-sharded rounds do not support the drift layer yet"
        )
    if client_mesh is not None and ds.train.shape[0] % client_mesh.size != 0:
        raise ValueError(
            f"client axis ({ds.train.shape[0]} sensors) must divide the "
            f"({client_mesh.size})-device client mesh"
        )

    def round_fn(state: HFLState, _) -> tuple[HFLState, RoundMetrics]:
        if fault_on:
            key, k_mob, k_train, k_byz, k_crash, k_erase = jax.random.split(
                state.key, 6
            )
        else:
            key, k_mob, k_train = jax.random.split(state.key, 3)
        dep = state.dep
        if cfg.fog_mobility:
            dep = topo.gauss_markov_step(k_mob, dep, cfg.deployment)
        if drift_on:
            dep = topo.current_advection_step(
                dep, cfg.deployment, dr.sensor_current_m_s
            )

        if drift_on:
            # Frozen round membership, live gateway physics (see
            # hfl.make_round_fn — identical cadence logic).
            t_f = state.t.astype(jnp.float32)
            cadence = jnp.maximum(
                jnp.asarray(dr.reassoc_every, jnp.float32), 1.0
            )
            refresh = jnp.mod(t_f, cadence) < 0.5
            fresh = assoc.flat_association(dep, cfg.channel)
            assoc_ok = jnp.where(refresh, fresh.participates, state.assoc_ok)
            fa = assoc.assigned_flat_association(dep, cfg.channel, assoc_ok)
        else:
            assoc_ok = state.assoc_ok
            fa = assoc.flat_association(dep, cfg.channel)
        alive = state.battery > cfg.energy.e_min_j
        active = fa.participates & alive
        if fault_on:
            active = active & ~flt.draw_crash(
                k_crash, alive.shape[0], fl.crash_prob
            )

        flat0, unravel = ravel_pytree(state.params)
        d = flat0.shape[0]
        n = ds.train.shape[0]
        keys = jax.random.split(k_train, n)
        train = ds.train
        if drift_on:
            train = train * (1.0 + dr.covariate_shift * t_f)

        active_f = active.astype(jnp.float32)
        # Erasure after feasibility: energy charged, EF advanced, weight 0.
        if fault_on:
            erased = active & flt.draw_erasure(k_erase, n, fl.erasure_prob)
        else:
            erased = jnp.zeros_like(active)
        delivered = active & ~erased
        weights = ds.n_samples * delivered.astype(jnp.float32)
        gateway_id = jnp.zeros((ds.train.shape[0],), jnp.int32)

        if client_mesh is None:
            deltas, stats = clients_fn(state.params, train, keys)
            losses = stats["loss"]
            if fault_on:
                deltas = flt.corrupt_deltas(
                    k_byz, deltas, fl, prev_delta=state.prev_delta
                )
            n_nonfinite = jnp.sum(
                (delivered & flt.nonfinite_rows(deltas)).astype(jnp.int32)
            )
            if cfg.robust == "mean":
                fog_sum, fog_weight, new_err = agg.compress_and_accumulate(
                    deltas, state.err, gateway_id, weights, 1,
                    cfg.compressor, chunk=cfg.client_chunk,
                )
                fog_delta = fog_sum / jnp.maximum(fog_weight, 1e-12)[:, None]
            else:
                fog_delta, _, new_err = agg.robust_compress_and_aggregate(
                    deltas, state.err, gateway_id, weights, 1,
                    cfg.compressor, cfg.trim_frac, cfg.robust,
                    chunk=cfg.client_chunk,
                )
        else:
            sharded = shard_map_compat(
                lambda p, dat, kk, e, w, fid: _clients_round(
                    clients_fn, p, dat, kk, e, w, fid, 1,
                    cfg.compressor, axis="data", chunk=cfg.client_chunk,
                ),
                mesh=client_mesh,
                in_specs=(P(), P("data"), P("data"), P("data"),
                          P("data"), P("data")),
                out_specs=(P(), P(), P("data"), P("data")),
            )
            fog_delta, _, new_err, stats = sharded(
                state.params, train, keys, state.err, weights, gateway_id
            )
            losses = stats["loss"]
            n_nonfinite = jnp.int32(0)
        new_err = jnp.where(active[:, None], new_err, state.err)
        mean_delta = fog_delta[0]
        if cfg.server_opt == "adam":
            # FedAdam [34] at the gateway: delta is the pseudo-gradient.
            incr, server = srv.adam_update(
                mean_delta, state.server, lr=cfg.server_lr
            )
        else:
            incr, server = mean_delta, state.server
        new_params = unravel(flat0 + incr)

        l_u = comp.payload_bits(d, cfg.compressor)
        e_up = en.tx_energy_j(l_u, fa.dist_m, cfg.channel, cfg.energy)
        e_up = jnp.where(active, e_up, 0.0)
        e_total = jnp.sum(e_up)

        lat_up = jnp.max(
            jnp.where(active, en.link_latency_s(l_u, fa.dist_m, cfg.channel), 0.0)
        )
        flops = as_detector(loss_fn).train_flops(
            state.params, ds.train.shape[1], cfg.batch_size, cfg.local_epochs
        )
        e_comp = en.compute_energy_j(jnp.float32(flops), cfg.energy)
        spent = e_up + jnp.where(active, e_comp, 0.0)
        battery, _ = en.battery_step(state.battery, spent, cfg.energy)

        metrics = RoundMetrics(
            loss=jnp.sum(losses * active_f) / jnp.maximum(jnp.sum(active_f), 1.0),
            e_s2f=e_total,
            e_f2f=jnp.zeros(()),
            e_f2g=jnp.zeros(()),
            e_total=e_total,
            latency_s=lat_up + flops / cfg.compute_rate_flops,
            participation=jnp.mean(active_f),
            coop_links=jnp.zeros((), jnp.int32),
            battery_min=jnp.min(battery),
            n_nonfinite=n_nonfinite,
            n_erased=jnp.sum(erased.astype(jnp.int32)),
            global_finite=jnp.all(jnp.isfinite(flat0 + incr)),
        )
        prev_delta = incr if adaptive else state.prev_delta
        return (
            HFLState(
                new_params, new_err, battery, dep, key, server,
                state.assoc_fog, assoc_ok, state.t + 1, prev_delta,
            ),
            metrics,
        )

    return round_fn


def train_flat(
    key: jax.Array,
    init_params: Params,
    loss_fn: LossFn,
    ds: SensorDataset,
    cfg: HFLConfig,
    *,
    client_mesh: Mesh | None = None,
) -> tuple[Params, RoundMetrics]:
    from repro.core.hfl import init_state

    state = init_state(key, init_params, cfg)
    round_fn = make_flat_round_fn(loss_fn, ds, cfg, client_mesh=client_mesh)
    final, metrics = jax.lax.scan(round_fn, state, None, length=cfg.rounds)
    return final.params, metrics


# ---------------------------------------------------------------------------
# SCAFFOLD
# ---------------------------------------------------------------------------

class ScaffoldTrainState(NamedTuple):
    fl: HFLState
    ctrl: scf.ScaffoldState


def train_scaffold(
    key: jax.Array,
    init_params: Params,
    loss_fn: LossFn,
    ds: SensorDataset,
    cfg: HFLConfig,
) -> tuple[Params, RoundMetrics]:
    """SCAFFOLD over feasible direct links (released-trace baseline).

    SCAFFOLD's deltas are pytrees averaged without the compress path, so
    the fault layer ravels them to flat rows first: Byzantine corruption /
    the isfinite guard / the robust reduce all act on the flat stream,
    and the mean is unravelled back.  With the fault layer statically
    inactive and ``robust == "mean"`` the legacy tree path runs untouched.
    """
    from repro.core.hfl import init_state

    if cfg.robust not in ("mean", "trimmed", "median"):
        raise ValueError(
            f"robust must be 'mean', 'trimmed' or 'median', got "
            f"{cfg.robust!r}"
        )
    fl_cfg = cfg.faults
    fault_on = fl_cfg.is_active
    fault_path = fault_on or cfg.robust != "mean"
    dr = cfg.drift
    drift_on = dr.is_active
    adaptive = fault_on and fl_cfg.byz_mode == "adaptive"

    n = ds.train.shape[0]
    state = ScaffoldTrainState(
        fl=init_state(key, init_params, cfg),
        ctrl=scf.init_state(init_params, n),
    )

    def round_fn(s: ScaffoldTrainState, _):
        st = s.fl
        if fault_on:
            key, k_mob, k_train, k_byz, k_crash, k_erase = jax.random.split(
                st.key, 6
            )
        else:
            key, k_mob, k_train = jax.random.split(st.key, 3)
        dep = st.dep
        if cfg.fog_mobility:
            dep = topo.gauss_markov_step(k_mob, dep, cfg.deployment)
        if drift_on:
            dep = topo.current_advection_step(
                dep, cfg.deployment, dr.sensor_current_m_s
            )
        if drift_on:
            t_f = st.t.astype(jnp.float32)
            cadence = jnp.maximum(
                jnp.asarray(dr.reassoc_every, jnp.float32), 1.0
            )
            refresh = jnp.mod(t_f, cadence) < 0.5
            fresh = assoc.flat_association(dep, cfg.channel)
            assoc_ok = jnp.where(refresh, fresh.participates, st.assoc_ok)
            fa = assoc.assigned_flat_association(dep, cfg.channel, assoc_ok)
        else:
            assoc_ok = st.assoc_ok
            fa = assoc.flat_association(dep, cfg.channel)
        active = fa.participates & (st.battery > cfg.energy.e_min_j)
        if fault_on:
            active = active & ~flt.draw_crash(k_crash, n, fl_cfg.crash_prob)
        active_f = active.astype(jnp.float32)

        keys = jax.random.split(k_train, n)
        train = ds.train
        if drift_on:
            train = train * (1.0 + dr.covariate_shift * t_f)

        def client_step(data, k, c_i):
            batches = multi_epoch_batches(
                k, data, cfg.batch_size, cfg.local_epochs
            )
            p1, new_ci, loss = scf.scaffold_local(
                loss_fn, st.params, batches, cfg.lr, s.ctrl.c_global, c_i
            )
            delta = jax.tree_util.tree_map(lambda a, b: a - b, p1, st.params)
            dc = jax.tree_util.tree_map(lambda a, b: a - b, new_ci, c_i)
            return delta, new_ci, dc, loss

        deltas, new_ci, dcs, losses = jax.vmap(client_step)(
            train, keys, s.ctrl.c_local
        )
        if fault_on:
            erased = active & flt.draw_erasure(k_erase, n, fl_cfg.erasure_prob)
        else:
            erased = jnp.zeros_like(active)
        delivered = active & ~erased
        delivered_f = delivered.astype(jnp.float32)
        weights = ds.n_samples * delivered_f

        if fault_path:
            flat_deltas = jax.vmap(lambda t: ravel_pytree(t)[0])(deltas)
            if fault_on:
                flat_deltas = flt.corrupt_deltas(
                    k_byz, flat_deltas, fl_cfg, prev_delta=st.prev_delta
                )
            finite = ~flt.nonfinite_rows(flat_deltas)
            n_nonfinite = jnp.sum((delivered & ~finite).astype(jnp.int32))
            w_del = weights * finite.astype(jnp.float32)
            safe = jnp.where(finite[:, None], flat_deltas, 0.0)
            if cfg.robust == "mean":
                mean_flat = agg.weighted_mean(safe, w_del)
            else:
                fog_out, _ = kops.robust_aggregate(
                    safe, jnp.zeros((n,), jnp.int32), w_del, 1,
                    cfg.trim_frac, cfg.robust,
                    use_pallas=cfg.compressor.use_pallas,
                    interpret=cfg.compressor.interpret,
                )
                mean_flat = fog_out[0]
            _, unravel_delta = ravel_pytree(
                jax.tree_util.tree_map(lambda x: x[0], deltas)
            )
            mean_delta = unravel_delta(mean_flat)
        else:
            n_nonfinite = jnp.int32(0)
            mean_delta = agg.weighted_mean(deltas, weights)
        new_params = jax.tree_util.tree_map(
            lambda p, dlt: p + dlt, st.params, mean_delta
        )
        # c <- c + (1/N) sum delivered dc (== active with the faults off)
        frac = jnp.sum(delivered_f) / n
        mean_dc = agg.weighted_mean(dcs, delivered_f)
        new_cg = jax.tree_util.tree_map(
            lambda c, dc: c + frac * dc, s.ctrl.c_global, mean_dc
        )
        keep = active.reshape((-1,) + (1,) * 0)
        new_cl = jax.tree_util.tree_map(
            lambda old, new: jnp.where(
                active.reshape((-1,) + (1,) * (new.ndim - 1)), new, old
            ),
            s.ctrl.c_local,
            new_ci,
        )
        del keep

        flat0, _ = ravel_pytree(st.params)
        l_u = comp.payload_bits(flat0.shape[0], cfg.compressor)
        e_up = jnp.where(
            active, en.tx_energy_j(l_u, fa.dist_m, cfg.channel, cfg.energy), 0.0
        )
        battery, _ = en.battery_step(st.battery, e_up, cfg.energy)
        metrics = RoundMetrics(
            loss=jnp.sum(losses * active_f) / jnp.maximum(jnp.sum(active_f), 1.0),
            e_s2f=jnp.sum(e_up),
            e_f2f=jnp.zeros(()),
            e_f2g=jnp.zeros(()),
            e_total=jnp.sum(e_up),
            latency_s=jnp.zeros(()),
            participation=jnp.mean(active_f),
            coop_links=jnp.zeros((), jnp.int32),
            battery_min=jnp.min(battery),
            n_nonfinite=n_nonfinite,
            n_erased=jnp.sum(erased.astype(jnp.int32)),
            global_finite=jnp.all(
                jnp.isfinite(ravel_pytree(new_params)[0])
            ),
        )
        # Adaptive colluders observe the realised global movement (the
        # flat mean delta; only computed on the fault path).
        prev_delta = mean_flat if adaptive else st.prev_delta
        return (
            ScaffoldTrainState(
                HFLState(
                    new_params, st.err, battery, dep, key, st.server,
                    st.assoc_fog, assoc_ok, st.t + 1, prev_delta,
                ),
                scf.ScaffoldState(new_cg, new_cl),
            ),
            metrics,
        )

    final, metrics = jax.lax.scan(round_fn, state, None, length=cfg.rounds)
    return final.fl.params, metrics


# ---------------------------------------------------------------------------
# Centralised oracle
# ---------------------------------------------------------------------------

def train_centralised(
    key: jax.Array,
    init_params: Params,
    loss_fn: LossFn,
    ds: SensorDataset,
    cfg: HFLConfig,
) -> tuple[Params, jax.Array, jax.Array]:
    """All-data oracle at the gateway.

    Returns (params, losses (T,), upload_energy_j scalar).  Energy is the
    one-time raw-data upload through each sensor's cheapest feasible path.
    """
    kd, kt = jax.random.split(key)
    dep = topo.sample_deployment(kd, cfg.deployment)

    # Raw-data upload energy, cheapest feasible path per sensor.
    raw_bits = ds.train.shape[1] * ds.train.shape[2] * 32.0
    flat = assoc.flat_association(dep, cfg.channel)
    fog = assoc.nearest_feasible_fog(dep, cfg.channel)
    e_direct = en.tx_energy_j(raw_bits, flat.dist_m, cfg.channel, cfg.energy)
    e_relay = en.tx_energy_j(
        raw_bits, fog.dist_m, cfg.channel, cfg.energy
    ) + en.tx_energy_j(
        raw_bits, fog.fog_gateway_dist_m[fog.fog_id], cfg.channel, cfg.energy
    )
    e_path = jnp.minimum(
        jnp.where(flat.participates, e_direct, jnp.inf),
        jnp.where(fog.participates, e_relay, jnp.inf),
    )
    upload_energy = jnp.sum(jnp.where(jnp.isfinite(e_path), e_path, 0.0))

    pooled = ds.train.reshape(-1, ds.train.shape[-1])

    def epoch(carry, k):
        params = carry
        params, loss = local_sgd(
            loss_fn,
            params,
            multi_epoch_batches(k, pooled, cfg.batch_size, 1),
            cfg.lr,
        )
        return params, loss

    keys = jax.random.split(kt, cfg.rounds * cfg.local_epochs)
    params, losses = jax.lax.scan(epoch, init_params, keys)
    return params, losses, upload_energy
