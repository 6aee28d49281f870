"""Hierarchical federated learning main loop (paper Algorithm 1).

The whole federated round is ONE jitted function; training scans it over T
rounds.  Clients are a vmapped leading axis (their local SGD runs in
parallel), fog clusters are segment-sum groups, and the three cooperation
rules from Sec. V-B drive the mixing step.  Per-round energy (Eqs. 17-20),
latency (Eq. 21), participation, and battery dynamics are all recorded.

The sensor side of a round is TWO fused operators.  Local training
(Eq. 12) runs through :func:`repro.optim.sgd.make_client_solver`: for the
paper autoencoder the whole E-epoch SGD phase of every client is one
VMEM-resident kernel launch (``kernels/fused_local_train``, jnp oracle
``kernels/ref.local_train_ref``) that indexes each client's resident
window per minibatch instead of gathering a dense ``(E * nb, bs, D)``
batch stream (detectors that are not ``fusable`` take a per-client
scan).  Compression (Eq. 30) and fog aggregation (Eq. 13) then run as
the second fused operator —
:func:`repro.core.aggregation.compress_and_aggregate` — so the dense
per-client reconstructions never materialise either.
``loss_fn`` is a plain loss or a :class:`repro.models.detector.Detector`
(the Anomaly Transformer trains on stride-1 windows); the compute term of
the energy and latency model counts that detector's own operations.

With ``HFLConfig.client_chunk`` below N (and no fault layer, mean fog
reduce, one device) the client side is ONE phase per chunk inside a scan:
train the chunk, emit its wire, accumulate into the fog buffers, update
its rows of the error-feedback state in place
(:func:`repro.core.aggregation.client_chunk_scan`), so only that state is
(N, d).

Pass ``client_mesh`` (a 1-D ``("data",)`` mesh, see
``launch/sharding.client_mesh``) to :func:`train` / :func:`make_round_fn`
to shard the client axis over devices: local SGD + compression run
per-shard under ``shard_map`` and the fog buffers are reduced with psum
collectives, the multi-device analogue of the sensor->fog acoustic hop.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import aggregation as agg
from repro.core import association as assoc
from repro.core import channel as ch
from repro.core import compression as comp
from repro.core import cooperation as coop
from repro.core import drift as drf
from repro.core import energy as en
from repro.core import faults as flt
from repro.core import topology as topo
from repro.data.synthetic import SensorDataset
from repro.launch.mesh import shard_map_compat
from repro.models.detector import Detector, as_detector
from repro.optim import server as srv
from repro.optim.sgd import LocalTrainConfig, make_client_solver

Params = Any
LossFn = Callable[[Params, jax.Array], jax.Array]


@dataclasses.dataclass(frozen=True)
class HFLConfig:
    """Round-loop configuration — a pytree split into swept vs static.

    LEAVES (traceable, stackable along a config axis — see
    ``Engine.sweep``): ``lr``, ``prox_mu``, ``server_lr``,
    ``compute_rate_flops``, ``trim_frac`` and the nested ``compressor``
    (its ``rho_s``), ``channel``, ``energy``, ``faults`` pytrees.
    Everything shape- or structure-bearing — rule enum, round/epoch/batch
    counts, solver and backend flags, deployment geometry, the ``robust``
    aggregation rule — is static aux data: configs that differ there
    belong to different sweep shape-classes and are never co-batched.

    Robustness: ``robust`` selects the fog reduce — ``"mean"`` (Eq. 13
    weighted mean, the default), ``"trimmed"`` (coordinate-wise weighted
    trimmed mean, cutting ``trim_frac`` of the member weight from each
    end), or ``"median"``.  ``faults`` injects crashes / Byzantine deltas /
    packet erasure (see :mod:`repro.core.faults`); when it is statically
    inactive and ``robust == "mean"`` the round loop is bit-identical to
    the legacy path (same PRNG splits).

    Dynamic world: ``drift`` (see :mod:`repro.core.drift`) advects the
    sensors in a deterministic current inside the round scan, freezes the
    sensor->fog assignment between ``reassoc_every``-round re-association
    refreshes (stale assignment, live physics), and applies a per-round
    covariate-shift schedule to the client training windows.  The layer
    is deterministic — it consumes no PRNG keys — so with
    ``drift.is_active`` False the round is bit-identical to the legacy
    path, and a neutral-active cell (zero rates, unit cadence) pins
    bit-identical too.
    """

    rule: coop.CoopRule = coop.CoopRule.SELECTIVE
    rounds: int = 20
    local_epochs: int = 5            # E
    batch_size: int = 32
    lr: float | Any = 0.01           # eta
    prox_mu: float | Any = 0.0       # >0 => FedProx local solver
    server_opt: str = "sgd"          # "sgd" (FedAvg identity) | "adam" (FedAdam [34])
    server_lr: float | Any = 1e-2
    local_solver: LocalTrainConfig = LocalTrainConfig()
    compressor: comp.CompressorConfig = comp.CompressorConfig()
    fog_mobility: bool = True
    compute_rate_flops: float | Any = 1e8  # embedded-DSP local compute rate
    # Fog exchange payloads are full precision in the paper (Sec. VI-A).
    channel: ch.ChannelParams = ch.ChannelParams()
    energy: en.EnergyParams = en.EnergyParams()
    deployment: topo.DeploymentParams = topo.DeploymentParams()
    robust: str = "mean"             # fog reduce: mean | trimmed | median
    trim_frac: float | Any = 0.0     # weight fraction cut per end (trimmed)
    faults: flt.FaultConfig = flt.FaultConfig()
    drift: drf.DriftConfig = drf.DriftConfig()
    # Client-phase memory bound: compress/accumulate scans the client axis
    # in chunks of this many sensors, so transient HBM/VMEM high-water
    # marks scale with the chunk, not the fleet.  None (or >= N) keeps the
    # one-shot path bit-identically; STATIC (it is shape-bearing).  Under
    # ``shard_clients`` the chunk applies within each shard's local slice.
    client_chunk: int | None = None

    def __post_init__(self) -> None:
        if self.robust not in ("mean", "trimmed", "median"):
            raise ValueError(
                f"robust must be 'mean', 'trimmed' or 'median', got "
                f"{self.robust!r}"
            )
        # Concrete values only: trim_frac is a sweep leaf, so traced /
        # stacked values pass (``__post_init__`` re-runs on unflatten).
        tf = self.trim_frac
        if isinstance(tf, (int, float)) and not 0.0 <= tf < 0.5:
            raise ValueError(
                "trim_frac cuts a weight fraction from EACH end and must "
                f"be in [0, 0.5), got {tf!r}"
            )
        cc = self.client_chunk
        if cc is not None and (not isinstance(cc, int) or cc < 1):
            raise ValueError(
                f"client_chunk must be None or a positive int, got {cc!r}"
            )

    def replace(self, **kw: Any) -> "HFLConfig":
        return dataclasses.replace(self, **kw)


_HFL_LEAF_FIELDS = (
    "lr", "prox_mu", "server_lr", "compute_rate_flops",
    "compressor", "channel", "energy", "trim_frac", "faults", "drift",
)
_HFL_AUX_FIELDS = (
    "rule", "rounds", "local_epochs", "batch_size", "server_opt",
    "local_solver", "fog_mobility", "deployment", "robust", "client_chunk",
)


def _hfl_cfg_flatten(c: HFLConfig):
    return (
        tuple(getattr(c, f) for f in _HFL_LEAF_FIELDS),
        tuple(getattr(c, f) for f in _HFL_AUX_FIELDS),
    )


def _hfl_cfg_unflatten(aux, children) -> HFLConfig:
    kw = dict(zip(_HFL_LEAF_FIELDS, children))
    kw.update(zip(_HFL_AUX_FIELDS, aux))
    return HFLConfig(**kw)


jax.tree_util.register_pytree_node(
    HFLConfig, _hfl_cfg_flatten, _hfl_cfg_unflatten
)


class RoundMetrics(NamedTuple):
    loss: jax.Array
    e_s2f: jax.Array          # Eq. 17
    e_f2f: jax.Array          # Eq. 18
    e_f2g: jax.Array          # Eq. 19
    e_total: jax.Array        # Eq. 20
    latency_s: jax.Array      # Eq. 21
    participation: jax.Array
    coop_links: jax.Array     # number of active fog-to-fog exchanges
    battery_min: jax.Array
    # Robustness counters (zero / True on the clean legacy path):
    n_nonfinite: jax.Array    # delivered deltas carrying NaN/Inf (zeroed)
    n_erased: jax.Array       # transmitted packets lost to erasure
    global_finite: jax.Array  # bool — global params finite after the round


# A detector with per-round stats beyond the loss (the Anomaly Transformer's
# ``assdis``) gets them averaged over active clients in ``detector_stats``.
DetectorRoundMetrics = NamedTuple(
    "DetectorRoundMetrics",
    [*RoundMetrics.__annotations__.items(), ("detector_stats", dict)],
)


class HFLState(NamedTuple):
    params: Params            # global model theta^t
    err: jax.Array            # (N, d) error-feedback buffers
    battery: jax.Array        # (N,) residual energy
    dep: topo.Deployment
    key: jax.Array
    server: srv.ServerOptState  # gateway optimiser state (FedAdam)
    # Dynamic-world carry (zeros when drift/adaptive attack are off; the
    # drift layer refreshes the assignment at round 0 before first use):
    assoc_fog: jax.Array      # (N,) int32 — frozen sensor->fog assignment
    assoc_ok: jax.Array       # (N,) bool — feasible at assignment time
    t: jax.Array              # () int32 — round counter
    prev_delta: jax.Array     # (d,) last global delta (adaptive colluders)


def init_state(
    key: jax.Array, params: Params, cfg: HFLConfig
) -> HFLState:
    kd, kr = jax.random.split(key)
    dep = topo.sample_deployment(kd, cfg.deployment)
    flat, _ = ravel_pytree(params)
    n = cfg.deployment.n_sensors
    return HFLState(
        params=params,
        err=jnp.zeros((n, flat.shape[0]), flat.dtype),
        battery=jnp.full((n,), cfg.energy.e_init_j),
        dep=dep,
        key=kr,
        server=srv.init_state(flat.shape[0]),
        assoc_fog=jnp.zeros((n,), jnp.int32),
        assoc_ok=jnp.zeros((n,), bool),
        t=jnp.int32(0),
        prev_delta=jnp.zeros((flat.shape[0],), flat.dtype),
    )


def _client_train_fn(loss_fn: LossFn | Detector, cfg: HFLConfig):
    """Batched client phase: E-epoch local SGD from the broadcast params
    for every client it is given, returning flat deltas and the
    detector's per-client stats (fused kernel path for the paper AE by
    default; see :func:`repro.optim.sgd.make_client_solver`)."""
    return make_client_solver(
        as_detector(loss_fn),
        batch_size=cfg.batch_size,
        epochs=cfg.local_epochs,
        lr=cfg.lr,
        prox_mu=cfg.prox_mu,
        solver=cfg.local_solver,
    )


def _clients_round(
    clients_fn, params, data, keys, err, weights, fog_id, n_fog, cc,
    axis: str | None = None,
    chunk: int | None = None,
):
    """Train every client and fuse compression into the fog reduction.

    The sensor side in two fused operators: ``clients_fn`` (the batched
    local-train solver from :func:`_client_train_fn`) emits the flat
    deltas, which chain straight into the fused compress-and-aggregate.
    With ``axis`` set this is the shard_map body: each shard trains its
    slice of the client axis and contributes partial fog sums; the psum
    pair is the sensor->fog hop (cf. aggregation.hierarchical_mean).
    Returns (fog_delta (n_fog, d) — Eq. 13 cluster means — fog_weight,
    new_err (N_local, d), per-client stats {"loss": (N_local,), ...}).
    """
    deltas, stats = clients_fn(params, data, keys)
    fog_delta, fog_weight, new_err = agg.compress_and_aggregate(
        deltas, err, fog_id, weights, n_fog, cc, axis=axis, chunk=chunk
    )
    return fog_delta, fog_weight, new_err, stats


def comm_latency_s(
    l_u: jax.Array,
    l_full: jax.Array,
    active: jax.Array,
    sensor_dist_m: jax.Array,
    decision: coop.CoopDecision,
    fog_active: jax.Array,
    fog_gateway_dist_m: jax.Array,
    channel: ch.ChannelParams,
) -> jax.Array:
    """Eq. 21 communication term: the slowest active parallel link per
    tier (sensor->fog uplink, fog<->fog exchange, fog->gateway).

    Every tier masks on the links that actually carry a payload.  In
    particular the fog-to-fog tier masks on ``cooperates & fog_active``,
    matching the Eq. 18 energy term: an EMPTY fog cluster has no model to
    exchange, so a phantom pairing with a distant partner must not set the
    round's latency.
    """
    lat_up = jnp.max(
        jnp.where(
            active, en.link_latency_s(l_u, sensor_dist_m, channel), 0.0
        )
    )
    lat_ff = jnp.max(
        jnp.where(
            decision.cooperates & fog_active,
            en.link_latency_s(l_full, decision.dist_m, channel),
            0.0,
        )
    )
    lat_fg = jnp.max(
        jnp.where(
            fog_active,
            en.link_latency_s(l_full, fog_gateway_dist_m, channel),
            0.0,
        )
    )
    return jnp.maximum(jnp.maximum(lat_up, lat_ff), lat_fg)


def make_round_fn(
    loss_fn: LossFn | Detector,
    ds: SensorDataset,
    cfg: HFLConfig,
    *,
    client_mesh: Mesh | None = None,
) -> Callable[[HFLState, None], tuple[HFLState, RoundMetrics]]:
    """Build the jittable single-round function (Algorithm 1).

    ``client_mesh``: optional 1-D ``("data",)`` mesh; when given, the
    client axis (local SGD + fused compression) is sharded over its
    devices with fog reduction via psum collectives.  Requires the sensor
    count to divide the mesh size.
    """

    n_fog = cfg.deployment.n_fog
    detector = as_detector(loss_fn)
    clients_fn = _client_train_fn(detector, cfg)
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

        # --- 1. association + cooperation decisions (lines 1-7) ----------
        with jax.named_scope("round.associate"):
            if drift_on:
                # Stale assignment, live physics: refresh the carried
                # sensor->fog assignment every ``reassoc_every`` rounds (round
                # 0 always refreshes), then recompute distances / feasibility /
                # clusters from CURRENT geometry against the frozen fog id.
                t_f = state.t.astype(jnp.float32)
                cadence = jnp.maximum(
                    jnp.asarray(dr.reassoc_every, jnp.float32), 1.0
                )
                refresh = jnp.mod(t_f, cadence) < 0.5
                fresh = assoc.nearest_feasible_fog(dep, cfg.channel)
                assoc_fog = jnp.where(refresh, fresh.fog_id, state.assoc_fog)
                assoc_ok = jnp.where(refresh, fresh.participates, state.assoc_ok)
                fa = assoc.assigned_fog_association(
                    dep, cfg.channel, assoc_fog, assoc_ok
                )
            else:
                assoc_fog, assoc_ok = state.assoc_fog, state.assoc_ok
                fa = assoc.nearest_feasible_fog(dep, cfg.channel)
            alive = state.battery > cfg.energy.e_min_j
            active = fa.participates & alive
            if fault_on:
                # Crashed clients drop out like a dead battery: no training,
                # no transmission, no energy spend this round.
                active = active & ~flt.draw_crash(
                    k_crash, alive.shape[0], fl.crash_prob
                )
            # Cooperation sees ROUND-ACTIVE cluster sizes (battery included):
            # a cluster whose sensors are all dead this round holds no
            # aggregate to exchange, exactly like an empty one — so the
            # decision, the Eq. 15 mixing, and the Eq. 18/21 masks agree.
            c_active = jax.ops.segment_sum(
                active.astype(jnp.int32), fa.fog_id, num_segments=n_fog
            )
            decision = coop.decide(cfg.rule, dep.fog_pos, c_active, cfg.channel)

        # --- 2+3. local training, fused compression + fog aggregation
        # (lines 8-18, Eqs. 30 + 13 as one operator) -----------------------
        flat0, unravel = ravel_pytree(state.params)
        d = flat0.shape[0]
        n = ds.train.shape[0]
        keys = jax.random.split(k_train, n)
        train = ds.train
        if drift_on:
            # Deterministic covariate-shift schedule: the telemetry scale
            # drifts a fraction per round (zero shift multiplies by 1.0,
            # which is bit-exact).
            train = train * (1.0 + dr.covariate_shift * t_f)

        active_f = active.astype(jnp.float32)
        # Erasure strikes AFTER the SNR feasibility gate: the packet was
        # transmitted (energy still charged below, EF buffer still
        # advances) but the fog never decodes it — only the aggregation
        # weight vanishes.
        if fault_on:
            erased = active & flt.draw_erasure(k_erase, n, fl.erasure_prob)
        else:
            erased = jnp.zeros_like(active)
        delivered = active & ~erased
        weights = ds.n_samples * delivered.astype(jnp.float32)

        chunk = cfg.client_chunk
        if (client_mesh is None and not fault_on and cfg.robust == "mean"
                and chunk is not None and 0 < chunk < n):
            # One client phase per chunk: train, emit the wire, accumulate.
            # Only the EF state is (N, d); it is updated in place.
            def chunk_deltas(start):
                sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, chunk)  # noqa: E731
                with jax.named_scope("round.local_train"):
                    deltas_c, stats_c = clients_fn(state.params, sl(train), sl(keys))
                return deltas_c, (stats_c, flt.nonfinite_rows(deltas_c))

            with jax.named_scope("round.aggregate"):
                fog_sum, fog_weight, new_err, (stats, nonfinite) = (
                    agg.client_chunk_scan(
                        chunk_deltas, state.err, fa.fog_id, weights, active,
                        n_fog, cfg.compressor, chunk,
                    )
                )
            fog_delta = fog_sum / jnp.maximum(fog_weight, 1e-12)[:, None]
            n_nonfinite = jnp.sum((delivered & nonfinite).astype(jnp.int32))
        elif client_mesh is None:
            with jax.named_scope("round.local_train"):
                deltas, stats = clients_fn(state.params, train, keys)
            if fault_on:
                deltas = flt.corrupt_deltas(
                    k_byz, deltas, fl, prev_delta=state.prev_delta
                )
            n_nonfinite = jnp.sum(
                (delivered & flt.nonfinite_rows(deltas)).astype(jnp.int32)
            )
            with jax.named_scope("round.aggregate"):
                if cfg.robust == "mean":
                    fog_sum, fog_weight, new_err = agg.compress_and_accumulate(
                        deltas, state.err, fa.fog_id, weights, n_fog,
                        cfg.compressor, chunk=chunk,
                    )
                    fog_delta = fog_sum / jnp.maximum(fog_weight, 1e-12)[:, None]
                else:
                    fog_delta, fog_weight, new_err = (
                        agg.robust_compress_and_aggregate(
                            deltas, state.err, fa.fog_id, weights, n_fog,
                            cfg.compressor, cfg.trim_frac, cfg.robust,
                            chunk=chunk,
                        )
                    )
            # Non-participants keep their error buffer and contribute nothing.
            new_err = jnp.where(active[:, None], new_err, state.err)
        else:
            sharded = shard_map_compat(
                lambda p, dat, kk, e, w, fid: _clients_round(
                    clients_fn, p, dat, kk, e, w, fid, n_fog,
                    cfg.compressor, axis="data", chunk=chunk,
                ),
                mesh=client_mesh,
                in_specs=(P(), P("data"), P("data"), P("data"),
                          P("data"), P("data")),
                out_specs=(P(), P(), P("data"), P("data")),
            )
            with jax.named_scope("round.local_train_aggregate"):
                fog_delta, fog_weight, new_err, stats = sharded(
                    state.params, train, keys, state.err, weights, fa.fog_id
                )
            # Sharded deltas never leave their shard: the isfinite guard
            # inside compress_and_accumulate still protects, only the
            # counter is unavailable there.
            n_nonfinite = jnp.int32(0)
            new_err = jnp.where(active[:, None], new_err, state.err)
        losses = stats["loss"]

        with jax.named_scope("round.global"):
            fog_model = fog_delta + flat0[None, :]          # theta_m^{t+1/2}
            mixed = agg.cooperative_mix(fog_model, decision)  # Eq. 15

            # --- 4. global aggregation (Eq. 16, lines 19-21) -------------------
            # prev=flat0: a dead-network round (every cluster weightless) holds
            # the global model instead of collapsing it to zeros.
            new_flat = agg.global_aggregate(mixed, fog_weight, prev=flat0)
            if cfg.server_opt == "adam":
                # FedAdam [34]: the aggregated movement is a pseudo-gradient.
                incr, server = srv.adam_update(
                    new_flat - flat0, state.server, lr=cfg.server_lr
                )
                new_flat = flat0 + incr
            else:
                server = state.server
            new_params = unravel(new_flat)

        # --- 5. energy / latency / battery accounting ---------------------
        with jax.named_scope("round.energy"):
            l_u = comp.payload_bits(d, cfg.compressor)     # sensor uplink bits
            l_full = 32.0 * d                               # fog exchanges, dense
            e_up = en.tx_energy_j(l_u, fa.dist_m, cfg.channel, cfg.energy)
            e_up = jnp.where(active, e_up, 0.0)
            e_s2f = jnp.sum(e_up)

            fog_active = fog_weight > 0
            e_ff = en.tx_energy_j(l_full, decision.dist_m, cfg.channel, cfg.energy)
            e_ff = jnp.where(decision.cooperates & fog_active, e_ff, 0.0)
            e_f2f = jnp.sum(e_ff)

            e_fg = en.tx_energy_j(
                l_full, fa.fog_gateway_dist_m, cfg.channel, cfg.energy
            )
            e_fg = jnp.where(fog_active & fa.fog_gateway_feasible, e_fg, 0.0)
            e_f2g = jnp.sum(e_fg)

            # Latency (Eq. 21): slowest parallel link per tier + compute time.
            lat_comm = comm_latency_s(
                l_u, l_full, active, fa.dist_m, decision, fog_active,
                fa.fog_gateway_dist_m, cfg.channel,
            )
            flops = detector.train_flops(
                state.params, ds.train.shape[1], cfg.batch_size, cfg.local_epochs
            )
            lat_comp = flops / cfg.compute_rate_flops
            latency = lat_comm + lat_comp

            e_comp = en.compute_energy_j(jnp.float32(flops), cfg.energy)
            spent = e_up + jnp.where(active, e_comp, 0.0)
            battery, _ = en.battery_step(state.battery, spent, cfg.energy)

        metrics = RoundMetrics(
            loss=jnp.sum(losses * active_f) / jnp.maximum(jnp.sum(active_f), 1.0),
            e_s2f=e_s2f,
            e_f2f=e_f2f,
            e_f2g=e_f2g,
            e_total=e_s2f + e_f2f + e_f2g,
            latency_s=latency,
            participation=jnp.mean(active_f),
            coop_links=jnp.sum(decision.cooperates.astype(jnp.int32)),
            battery_min=jnp.min(battery),
            n_nonfinite=n_nonfinite,
            n_erased=jnp.sum(erased.astype(jnp.int32)),
            global_finite=jnp.all(jnp.isfinite(new_flat)),
        )
        extra = {
            k: jnp.sum(v * active_f) / jnp.maximum(jnp.sum(active_f), 1.0)
            for k, v in stats.items() if k != "loss"
        }
        if extra:
            metrics = DetectorRoundMetrics(*metrics, detector_stats=extra)
        # Adaptive colluders observe the realised global movement; other
        # modes leave the carried delta untouched (identical graph).
        prev_delta = new_flat - flat0 if adaptive else state.prev_delta
        return (
            HFLState(
                new_params, new_err, battery, dep, key, server,
                assoc_fog, assoc_ok, state.t + 1, prev_delta,
            ),
            metrics,
        )

    return round_fn


def train(
    key: jax.Array,
    init_params: Params,
    loss_fn: LossFn | Detector,
    ds: SensorDataset,
    cfg: HFLConfig,
    *,
    client_mesh: Mesh | None = None,
    store: Any | None = None,
    publish_every: int = 1,
    publish_offset: int = 0,
) -> tuple[Params, RoundMetrics]:
    """Run T federated rounds; returns (final params, stacked metrics).

    With ``store`` (a ``checkpoint.CheckpointStore``) the loop publishes
    the global params every ``publish_every`` rounds (step = round index +
    ``publish_offset``; the final round always publishes), which is what
    the serving hot-swap (``serving/service.ScoringService``) watches.
    Publishing runs the rounds as a Python loop over ONE jitted round
    function instead of a ``lax.scan`` — identical numerics, same single
    compilation, but with host-visible params between rounds.
    """
    state = init_state(key, init_params, cfg)
    round_fn = make_round_fn(loss_fn, ds, cfg, client_mesh=client_mesh)
    if store is None or cfg.rounds == 0:
        # scan handles length 0 cleanly (and 0 rounds publish nothing).
        final, metrics = jax.lax.scan(round_fn, state, None, length=cfg.rounds)
        return final.params, metrics

    # Donating the carry lets each round update the HFLState — the (N, d)
    # error buffer included — in place instead of copying it per round.
    # state.params aliases the caller's ``init_params`` buffers, which the
    # first donated call would invalidate, so copy that one leaf up front.
    state = state._replace(
        params=jax.tree_util.tree_map(jnp.copy, state.params)
    )
    step_fn = jax.jit(lambda s: round_fn(s, None), donate_argnums=0)
    rounds_metrics = []
    for t in range(cfg.rounds):
        state, m = step_fn(state)
        rounds_metrics.append(m)
        if (t + 1) % publish_every == 0 or t + 1 == cfg.rounds:
            store.publish(publish_offset + t + 1, state.params)
    metrics = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *rounds_metrics
    )
    return state.params, metrics
