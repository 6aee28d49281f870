"""Event-driven asynchronous federated rounds with staleness-aware merging.

The fourth round-loop family next to ``core/hfl.py`` (synchronous
hierarchical), ``core/flat_fl.py`` (star topology), and ``core/mesh_fl.py``
(TPU-mesh pods).  The paper's own physics motivates it: Eq. 21 latency
spreads widely across acoustic links, so a synchronous round is paced by
the *slowest* feasible path while fast near-gateway clusters idle.  Here
the loop is event-driven instead — each client's update travels for its
own Eq. 21 path latency, a bounded buffer triggers global aggregation when
``buffer_k`` updates land (or a timeout tick fires), and late updates are
merged with staleness-discounted weights ``w(tau) = (1 + tau)^(-alpha)``
where ``tau`` counts global model versions the update missed.

Simulation model (one jittable scan, vmappable over the Engine's
``(seed, deployment)`` trial grid):

* **Launch** — an idle, round-active client pulls the current global
  params, runs its E-epoch local phase through the SAME fused local-train
  solver as the synchronous loops (:func:`repro.optim.sgd.make_client_solver`),
  compresses through the SAME fused compress-and-aggregate kernel
  (:func:`repro.core.aggregation.compress_and_accumulate` with one segment
  per client, so the error-feedback state is bit-compatible), and puts the
  reconstruction "on the wire": it arrives ``compute + uplink latency``
  simulated seconds later.  Uplink energy and compute energy are charged
  to the battery at launch.
* **Fog tick** — the scan step fires when ``fog_k`` in-flight updates have
  landed (or ``fog_timeout_s`` passes): arrivals fold into persistent
  per-fog accumulators, discounted by their staleness at arrival.  This is
  the fog-local cadence.
* **Global merge** — when the number of buffered updates reaches
  ``buffer_k`` (clamped to what can still arrive) or ``timeout_s`` passes
  since the last merge, fog means are cooperatively mixed (Eq. 15) and
  aggregated at the gateway (Eq. 16, FedAdam optional), the accumulators
  drain, and the global version increments.  Fog cadence (``fog_k``) and
  global cadence (``buffer_k``) are decoupled knobs.

**Sync limit.**  With ``fog_k`` and ``buffer_k`` at the fleet size, no
staleness discount (``alpha = 0``) and infinite timeouts, every event
waits for all launched updates, merges them undiscounted, and relaunches
everyone from the new model — exactly Algorithm 1.  :func:`sync_limit`
builds that config and ``tests/test_async_fl.py`` pins the equivalence
against ``hfl.train`` to float tolerance.

All async knobs are traceable pytree leaves (``AsyncFLConfig`` is a
registered pytree like ``HFLConfig``), so ``Engine.sweep`` grids
``alpha`` x ``buffer_k`` x timeout cells in ONE compiled program per
shape-class, exactly like today's energy/compression sweeps.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from repro.core import aggregation as agg
from repro.core import association as assoc
from repro.core import compression as comp
from repro.core import cooperation as coop
from repro.core import energy as en
from repro.core import faults as flt
from repro.core import hfl
from repro.core import topology as topo
from repro.data.synthetic import SensorDataset
from repro.kernels import ops as kops
from repro.models.detector import as_detector
from repro.optim import server as srv

Params = Any
LossFn = Callable[[Params, jax.Array], jax.Array]

# "Never" for the timeout knobs: a finite sentinel keeps every arithmetic
# path (stacking, subtraction) inf-free while exceeding any simulated time
# a bounded scan can reach.
NEVER_S = 1e30


@dataclasses.dataclass(frozen=True)
class AsyncFLConfig:
    """Async round-family configuration — a pytree split into swept vs
    static, mirroring :class:`repro.core.hfl.HFLConfig`.

    LEAVES (traceable, stackable along a config axis — see
    ``Engine.sweep``): ``buffer_k``, ``fog_k``, ``alpha``, ``timeout_s``,
    ``fog_timeout_s``, ``tau_max`` plus everything swept inside the nested
    ``base`` config (lr, physics, ``rho_s``, faults, ...).  ``n_events`` —
    the scan length — is static aux data: configs that differ there belong
    to different sweep shape-classes.

    ``base.rounds`` is ignored by this family; ``n_events`` fog ticks are
    simulated instead (in the sync limit one tick == one round).

    Staleness policy: arrivals are discounted by ``(1 + tau)^(-alpha)``,
    and — the clipping policy on top — any update staler than ``tau_max``
    global versions is DROPPED (weight 0) instead of merely discounted.
    The default ``tau_max = NEVER_S`` keeps every update (the pure
    discount path, numerically unchanged).
    """

    base: hfl.HFLConfig = hfl.HFLConfig()
    n_events: int = 40                   # fog ticks to simulate (static)
    buffer_k: float | Any = 8.0          # global merge after this many updates
    fog_k: float | Any = 1.0             # fog tick fires when this many land
    alpha: float | Any = 0.5             # staleness exponent in (1+tau)^(-alpha)
    timeout_s: float | Any = NEVER_S     # global merge timeout (sim seconds)
    fog_timeout_s: float | Any = NEVER_S  # fog tick timeout (sim seconds)
    tau_max: float | Any = NEVER_S       # drop updates staler than this
    # Arrival clock (a LEAF, so it sweeps/stacks like the other knobs).
    # Scalar: extra seconds added to the physics clock (compute + Eq. 21
    # uplink latency); the 0.0 default is bit-identical to the legacy
    # clock.  A (N,) array REPLACES the physics clock with replayed
    # per-client launch->arrival delays — the hook that drives the loop
    # from a recorded :class:`repro.loadgen.traces.ArrivalTrace` instead
    # of the synthetic latency model (energy stays physics-based either
    # way).  The branch is on the leaf's RANK, which is static under jit.
    arrival_delay_s: float | Any = 0.0

    def replace(self, **kw: Any) -> "AsyncFLConfig":
        return dataclasses.replace(self, **kw)


_ASYNC_CHILD_FIELDS = (
    "base", "buffer_k", "fog_k", "alpha", "timeout_s", "fog_timeout_s",
    "tau_max", "arrival_delay_s",
)
_ASYNC_AUX_FIELDS = ("n_events",)


def _async_cfg_flatten(c: AsyncFLConfig):
    return (
        tuple(getattr(c, f) for f in _ASYNC_CHILD_FIELDS),
        tuple(getattr(c, f) for f in _ASYNC_AUX_FIELDS),
    )


def _async_cfg_unflatten(aux, children) -> AsyncFLConfig:
    kw = dict(zip(_ASYNC_CHILD_FIELDS, children))
    kw.update(zip(_ASYNC_AUX_FIELDS, aux))
    return AsyncFLConfig(**kw)


jax.tree_util.register_pytree_node(
    AsyncFLConfig, _async_cfg_flatten, _async_cfg_unflatten
)


def sync_limit(base: hfl.HFLConfig, n_events: int | None = None) -> AsyncFLConfig:
    """The synchronous limiting case of the async family.

    Fog tick and merge buffer both wait for the whole fleet, the
    staleness discount is off, timeouts never fire: every event is one
    Algorithm 1 round (pinned against ``hfl.train`` in the tests).
    """
    n = float(base.deployment.n_sensors)
    return AsyncFLConfig(
        base=base,
        n_events=base.rounds if n_events is None else n_events,
        buffer_k=n,
        fog_k=n,
        alpha=0.0,
        timeout_s=NEVER_S,
        fog_timeout_s=NEVER_S,
    )


class AsyncEventMetrics(NamedTuple):
    """Per-fog-tick record.  The first block mirrors
    :class:`repro.core.hfl.RoundMetrics` (and matches it term-for-term in
    the sync limit); the second block is async-specific."""

    loss: jax.Array           # mean loss over this tick's launches
    e_s2f: jax.Array          # Eq. 17 — charged at launch
    e_f2f: jax.Array          # Eq. 18 — charged at merge
    e_f2g: jax.Array          # Eq. 19 — charged at merge
    e_total: jax.Array        # Eq. 20
    latency_s: jax.Array      # Eq. 21-style per-tick latency metric
    participation: jax.Array
    coop_links: jax.Array     # active fog-to-fog exchanges (merge ticks)
    battery_min: jax.Array
    n_nonfinite: jax.Array    # launched deltas carrying NaN/Inf (zeroed)
    n_erased: jax.Array       # arrivals lost to packet erasure
    global_finite: jax.Array  # bool — global params finite after this tick
    # --- async-specific ---
    merged: jax.Array         # bool — did the gateway merge this tick
    n_launched: jax.Array     # clients that started a job this tick
    n_arrived: jax.Array      # updates that landed this tick
    staleness: jax.Array      # mean tau over this tick's arrivals
    event_s: jax.Array        # simulated duration of this tick
    t_sim: jax.Array          # simulated clock after this tick


class AsyncState(NamedTuple):
    # Shared with the synchronous families:
    params: Params            # global model theta^(v)
    err: jax.Array            # (N, d) error-feedback buffers
    battery: jax.Array        # (N,) residual energy
    dep: topo.Deployment
    key: jax.Array
    server: srv.ServerOptState
    # Event-driven extensions:
    version: jax.Array        # () int32 — global model version v
    t_now: jax.Array          # () f32 — simulated clock
    t_last_merge: jax.Array   # () f32
    pending: jax.Array        # () int32 — updates buffered since last merge
    busy: jax.Array           # (N,) bool — update in flight
    inflight: jax.Array       # (N, d) — compressed reconstruction on the wire
    arrive_t: jax.Array       # (N,) f32 — absolute arrival time (NEVER_S idle)
    base_version: jax.Array   # (N,) int32 — version the job trained from
    uplink_lat: jax.Array     # (N,) f32 — Eq. 21 uplink latency at launch
    launch_fog: jax.Array     # (N,) int32 — fog the update was sent to
    fog_sum: jax.Array        # (M, d) — staleness-weighted delta sums
    fog_w: jax.Array          # (M,) — buffered weight per fog
    fog_n: jax.Array          # (M,) int32 — buffered update count per fog
    # Robust-aggregation buffers (``base.robust != "mean"`` only; degenerate
    # (N, 0) / untouched otherwise): per-CLIENT weighted sums so the merge
    # can reduce addressable per-client means with the trimmed/median
    # statistic instead of the pre-summed fog buffers.
    cli_sum: jax.Array        # (N, d_or_0) — weighted arrival sums
    cli_w: jax.Array          # (N,) — accumulated arrival weight
    cli_fog: jax.Array        # (N,) int32 — fog of the latest arrival
    # Dynamic-world carry (zeros when drift/adaptive attack are off):
    assoc_fog: jax.Array      # (N,) int32 — frozen sensor->fog assignment
    assoc_ok: jax.Array       # (N,) bool — feasible at assignment time
    tick: jax.Array           # () int32 — fog-tick counter
    prev_delta: jax.Array     # (d,) last global delta (adaptive colluders)


def init_state(
    key: jax.Array, params: Params, acfg: AsyncFLConfig
) -> AsyncState:
    """Mirror of ``hfl.init_state`` (same key splits, so the sync limit is
    deployment-for-deployment identical) plus the event-driven extensions."""
    cfg = acfg.base
    kd, kr = jax.random.split(key)
    dep = topo.sample_deployment(kd, cfg.deployment)
    flat, _ = ravel_pytree(params)
    n = cfg.deployment.n_sensors
    m = cfg.deployment.n_fog
    d = flat.shape[0]
    return AsyncState(
        params=params,
        err=jnp.zeros((n, d), flat.dtype),
        battery=jnp.full((n,), cfg.energy.e_init_j),
        dep=dep,
        key=kr,
        server=srv.init_state(d),
        version=jnp.zeros((), jnp.int32),
        t_now=jnp.zeros(()),
        t_last_merge=jnp.zeros(()),
        pending=jnp.zeros((), jnp.int32),
        busy=jnp.zeros((n,), bool),
        inflight=jnp.zeros((n, d), flat.dtype),
        arrive_t=jnp.full((n,), NEVER_S),
        base_version=jnp.zeros((n,), jnp.int32),
        uplink_lat=jnp.zeros((n,)),
        launch_fog=jnp.zeros((n,), jnp.int32),
        fog_sum=jnp.zeros((m, d), flat.dtype),
        fog_w=jnp.zeros((m,)),
        fog_n=jnp.zeros((m,), jnp.int32),
        cli_sum=jnp.zeros(
            (n, d if cfg.robust != "mean" else 0), flat.dtype
        ),
        cli_w=jnp.zeros((n,)),
        cli_fog=jnp.zeros((n,), jnp.int32),
        assoc_fog=jnp.zeros((n,), jnp.int32),
        assoc_ok=jnp.zeros((n,), bool),
        tick=jnp.int32(0),
        prev_delta=jnp.zeros((d,), flat.dtype),
    )


def make_event_fn(
    loss_fn: LossFn,
    ds: SensorDataset,
    acfg: AsyncFLConfig,
) -> Callable[[AsyncState, None], tuple[AsyncState, AsyncEventMetrics]]:
    """Build the jittable single-event function (one fog tick)."""
    cfg = acfg.base
    n_fog = cfg.deployment.n_fog
    clients_fn = hfl._client_train_fn(loss_fn, cfg)
    if cfg.robust not in ("mean", "trimmed", "median"):
        raise ValueError(
            f"robust must be 'mean', 'trimmed' or 'median', got "
            f"{cfg.robust!r}"
        )
    fl = cfg.faults
    fault_on = fl.is_active       # STATIC: off => exact legacy event
    dr = cfg.drift
    drift_on = dr.is_active       # STATIC: off => exact legacy event
    adaptive = fault_on and fl.byz_mode == "adaptive"

    def event_fn(state: AsyncState, _) -> tuple[AsyncState, AsyncEventMetrics]:
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

        # --- association: who could launch / deliver this tick -----------
        if drift_on:
            # Re-association cadence counts fog ticks (the async round
            # analogue); tick 0 always refreshes.
            t_f = state.tick.astype(jnp.float32)
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
            # A crashed client cannot launch this tick; packets it already
            # has on the wire were sent before the crash and still travel.
            active = active & ~flt.draw_crash(
                k_crash, alive.shape[0], fl.crash_prob
            )
        active_f = active.astype(jnp.float32)

        flat0, unravel = ravel_pytree(state.params)
        d = flat0.shape[0]
        n = ds.train.shape[0]
        keys = jax.random.split(k_train, n)

        # --- launch: idle active clients pull theta^(v) and train --------
        # The fused kernels run for EVERY client (fixed shapes under jit);
        # non-launchers are masked out below, exactly like the inactive-
        # client masking of the synchronous loops.
        launch = active & ~state.busy
        launch_f = launch.astype(jnp.float32)
        train = ds.train
        if drift_on:
            train = train * (1.0 + dr.covariate_shift * t_f)
        deltas, stats = clients_fn(state.params, train, keys)
        losses = stats["loss"]
        if fault_on:
            # Byzantine corruption hits the raw delta before compression —
            # the attacker controls what leaves the sensor.
            deltas = flt.corrupt_deltas(
                k_byz, deltas, fl, prev_delta=state.prev_delta
            )
        n_nonfinite = jnp.sum(
            (launch & flt.nonfinite_rows(deltas)).astype(jnp.int32)
        )
        # One segment per client keeps the same fused compress kernel while
        # leaving each compressed reconstruction addressable for its own
        # in-flight journey (weights fold in at MERGE time, when the
        # staleness discount is known).  ``client_chunk`` bounds the
        # per-chunk kernel footprint exactly as in the synchronous loops.
        recon, new_err = agg.client_compress(
            deltas, state.err, cfg.compressor, chunk=cfg.client_chunk,
        )
        new_err = jnp.where(launch[:, None], new_err, state.err)
        inflight = jnp.where(launch[:, None], recon, state.inflight)

        # Transmission: the update lands after compute + uplink latency.
        l_u = comp.payload_bits(d, cfg.compressor)
        l_full = 32.0 * d
        flops = as_detector(loss_fn).train_flops(
            state.params, ds.train.shape[1], cfg.batch_size, cfg.local_epochs
        )
        lat_comp = jnp.float32(flops) / cfg.compute_rate_flops
        up_lat = en.link_latency_s(l_u, fa.dist_m, cfg.channel)
        delay = jnp.asarray(acfg.arrival_delay_s, jnp.float32)
        if delay.ndim > 0:
            # Trace replay: the recorded delay IS the end-to-end
            # launch->arrival time (compute included).
            up_eff = jnp.broadcast_to(delay, (n,))
            arr_t_new = state.t_now + up_eff
        else:
            # Physics clock (+0.0 scalar jitter = exact legacy numerics).
            up_eff = up_lat
            arr_t_new = state.t_now + lat_comp + up_lat + delay
        arrive_t = jnp.where(launch, arr_t_new, state.arrive_t)
        uplink_lat = jnp.where(launch, up_eff, state.uplink_lat)
        base_version = jnp.where(launch, state.version, state.base_version)
        launch_fog = jnp.where(launch, fa.fog_id, state.launch_fog)
        busy = state.busy | launch

        # Uplink + compute energy are spent at launch.
        e_up = en.tx_energy_j(l_u, fa.dist_m, cfg.channel, cfg.energy)
        e_up = jnp.where(launch, e_up, 0.0)
        e_comp = en.compute_energy_j(jnp.float32(flops), cfg.energy)
        spent = e_up + jnp.where(launch, e_comp, 0.0)
        battery, _ = en.battery_step(state.battery, spent, cfg.energy)

        # --- fog tick trigger: fog_k-th arrival or the fog timeout -------
        busy_t = jnp.where(busy, arrive_t, NEVER_S)
        n_busy = jnp.sum(busy.astype(jnp.int32))
        k_fog = jnp.clip(
            jnp.asarray(acfg.fog_k, jnp.float32),
            1.0,
            jnp.maximum(n_busy, 1).astype(jnp.float32),
        ).astype(jnp.int32)
        t_kth = jnp.take(jnp.sort(busy_t), k_fog - 1)
        t_tick = jnp.minimum(t_kth, state.t_now + acfg.fog_timeout_s)
        # Dead network (nothing in flight): the clock holds.
        t_tick = jnp.where(n_busy > 0, t_tick, state.t_now)
        # Merge propagation may have advanced the clock past a pending
        # arrival; time never runs backwards.
        t_tick = jnp.maximum(t_tick, state.t_now)

        arrived = busy & (arrive_t <= t_tick)
        # Erasure strikes at DELIVERY: the packet travelled (launch energy
        # was already charged, the EF buffer already advanced) but the fog
        # never decodes it — the client slot frees up, nothing folds in.
        if fault_on:
            lost = arrived & flt.draw_erasure(k_erase, n, fl.erasure_prob)
        else:
            lost = jnp.zeros_like(arrived)
        ok = arrived & ~lost
        ok_f = ok.astype(jnp.float32)
        n_arrived = jnp.sum(ok.astype(jnp.int32))

        # --- fold arrivals into the fog accumulators ---------------------
        # Staleness tau = versions the global model moved since the job's
        # anchor; w(tau) = (1 + tau)^(-alpha) discounts late updates, and
        # the clipping policy drops anything staler than tau_max outright.
        tau = (state.version - base_version).astype(jnp.float32)
        w_tau = (1.0 + tau) ** (-jnp.asarray(acfg.alpha, jnp.float32))
        w_tau = jnp.where(
            tau <= jnp.asarray(acfg.tau_max, jnp.float32), w_tau, 0.0
        )
        w = ds.n_samples * w_tau * ok_f
        fog_sum = state.fog_sum + jax.ops.segment_sum(
            inflight * w[:, None], launch_fog, num_segments=n_fog
        )
        fog_w = state.fog_w + jax.ops.segment_sum(
            w, launch_fog, num_segments=n_fog
        )
        fog_n = state.fog_n + jax.ops.segment_sum(
            ok.astype(jnp.int32), launch_fog, num_segments=n_fog
        )
        if cfg.robust == "mean":
            cli_sum, cli_w, cli_fog = state.cli_sum, state.cli_w, state.cli_fog
        else:
            # Per-client accumulation (w is zero for non-arrivals, so this
            # is a masked add); summing these over a fog reproduces fog_sum,
            # which is what makes trim 0 the weighted-mean equivalence.
            cli_sum = state.cli_sum + inflight * w[:, None]
            cli_w = state.cli_w + w
            cli_fog = jnp.where(ok, launch_fog, state.cli_fog)
        pending = state.pending + n_arrived
        busy = busy & ~arrived
        arrive_t = jnp.where(arrived, NEVER_S, arrive_t)

        # --- global merge trigger ---------------------------------------
        # buffer_k clamps to what can still arrive, so a depleted fleet
        # (or the sync limit with partial participation) still merges.
        reachable = pending + jnp.sum(busy.astype(jnp.int32))
        k_glob = jnp.minimum(
            jnp.asarray(acfg.buffer_k, jnp.float32),
            jnp.maximum(reachable, 1).astype(jnp.float32),
        )
        merge = (pending.astype(jnp.float32) >= k_glob) | (
            t_tick - state.t_last_merge >= acfg.timeout_s
        )

        # --- merge: fog means -> cooperative mix -> gateway (Eqs. 15-16) -
        # The cooperation decision sees the BUFFERED update counts — the
        # async analogue of the sync loop's round-active cluster sizes.
        decision = coop.decide(cfg.rule, dep.fog_pos, fog_n, cfg.channel)
        fog_has = fog_w > 0
        if cfg.robust == "mean":
            fog_delta = fog_sum / jnp.maximum(fog_w, 1e-12)[:, None]
            merge_w = fog_w
        else:
            # Robust reduce over the addressable per-client means: each
            # client's buffered arrivals collapse to a weighted mean first
            # (identical to its contribution to fog_sum), then the fog
            # reduce is the trimmed/median statistic.
            v_cli = cli_sum / jnp.maximum(cli_w, 1e-12)[:, None]
            fog_delta, merge_w = kops.robust_aggregate(
                v_cli, cli_fog, cli_w, n_fog, cfg.trim_frac, cfg.robust,
                use_pallas=cfg.compressor.use_pallas,
                interpret=cfg.compressor.interpret,
            )
        fog_model = fog_delta + flat0[None, :]
        mixed = agg.cooperative_mix(fog_model, decision)
        merged_flat = agg.global_aggregate(mixed, merge_w, prev=flat0)
        if cfg.server_opt == "adam":
            # FedAdam at the gateway; its state advances only on merges.
            incr, server_m = srv.adam_update(
                merged_flat - flat0, state.server, lr=cfg.server_lr
            )
            merged_flat = flat0 + incr
        else:
            server_m = state.server
        server = jax.tree_util.tree_map(
            lambda a, b: jnp.where(merge, a, b), server_m, state.server
        )
        new_flat = jnp.where(merge, merged_flat, flat0)
        new_params = unravel(new_flat)
        # The version only moves when the model does: a timeout merge over
        # an empty buffer holds theta and must not inflate staleness.
        did_move = merge & (jnp.sum(fog_w) > 0)
        version = state.version + did_move.astype(jnp.int32)

        # --- merge-side energy / latency (Eqs. 18, 19, 21) ---------------
        e_ff = en.tx_energy_j(l_full, decision.dist_m, cfg.channel, cfg.energy)
        e_f2f = jnp.where(
            merge,
            jnp.sum(jnp.where(decision.cooperates & fog_has, e_ff, 0.0)),
            0.0,
        )
        e_fg = en.tx_energy_j(
            l_full, fa.fog_gateway_dist_m, cfg.channel, cfg.energy
        )
        e_f2g = jnp.where(
            merge,
            jnp.sum(jnp.where(fog_has & fa.fog_gateway_feasible, e_fg, 0.0)),
            0.0,
        )
        lat_up = jnp.max(jnp.where(arrived, uplink_lat, 0.0))
        lat_ff = jnp.max(
            jnp.where(
                decision.cooperates & fog_has,
                en.link_latency_s(l_full, decision.dist_m, cfg.channel),
                0.0,
            )
        )
        lat_fg = jnp.max(
            jnp.where(
                fog_has,
                en.link_latency_s(l_full, fa.fog_gateway_dist_m, cfg.channel),
                0.0,
            )
        )
        merge_lat = jnp.where(merge, jnp.maximum(lat_ff, lat_fg), 0.0)
        # Eq. 21-comparable per-tick metric: slowest link among those that
        # carried a payload this tick, plus compute (== hfl.comm_latency_s
        # + compute in the sync limit).
        latency = jnp.maximum(lat_up, merge_lat) + lat_comp

        # The clock advances to the trigger, plus the merge propagation
        # (the new global model is only pullable once the fog exchange and
        # gateway upload complete).
        t_next = t_tick + merge_lat
        event_s = t_next - state.t_now

        # --- drain the buffer on merge -----------------------------------
        fog_sum = jnp.where(merge, 0.0, fog_sum)
        fog_w = jnp.where(merge, 0.0, fog_w)
        fog_n = jnp.where(merge, 0, fog_n)
        if cfg.robust != "mean":
            cli_sum = jnp.where(merge, 0.0, cli_sum)
            cli_w = jnp.where(merge, 0.0, cli_w)
        t_last_merge = jnp.where(merge, t_tick, state.t_last_merge)
        pending = jnp.where(merge, 0, pending)

        metrics = AsyncEventMetrics(
            loss=jnp.sum(losses * launch_f)
            / jnp.maximum(jnp.sum(launch_f), 1.0),
            e_s2f=jnp.sum(e_up),
            e_f2f=e_f2f,
            e_f2g=e_f2g,
            e_total=jnp.sum(e_up) + e_f2f + e_f2g,
            latency_s=latency,
            participation=jnp.mean(active_f),
            coop_links=jnp.where(
                merge, jnp.sum(decision.cooperates.astype(jnp.int32)), 0
            ),
            battery_min=jnp.min(battery),
            n_nonfinite=n_nonfinite,
            n_erased=jnp.sum(lost.astype(jnp.int32)),
            global_finite=jnp.all(jnp.isfinite(new_flat)),
            merged=merge,
            n_launched=jnp.sum(launch.astype(jnp.int32)),
            n_arrived=n_arrived,
            staleness=jnp.sum(tau * ok_f)
            / jnp.maximum(n_arrived.astype(jnp.float32), 1.0),
            event_s=event_s,
            t_sim=t_next,
        )
        new_state = AsyncState(
            params=new_params,
            err=new_err,
            battery=battery,
            dep=dep,
            key=key,
            server=server,
            version=version,
            t_now=t_next,
            t_last_merge=t_last_merge,
            pending=pending,
            busy=busy,
            inflight=inflight,
            arrive_t=arrive_t,
            base_version=base_version,
            uplink_lat=uplink_lat,
            launch_fog=launch_fog,
            fog_sum=fog_sum,
            fog_w=fog_w,
            fog_n=fog_n,
            cli_sum=cli_sum,
            cli_w=cli_w,
            cli_fog=cli_fog,
            assoc_fog=assoc_fog,
            assoc_ok=assoc_ok,
            tick=state.tick + 1,
            # Adaptive colluders observe the realised global movement,
            # which only happens on merge ticks.
            prev_delta=(
                jnp.where(merge, new_flat - flat0, state.prev_delta)
                if adaptive else state.prev_delta
            ),
        )
        return new_state, metrics

    return event_fn


def train(
    key: jax.Array,
    init_params: Params,
    loss_fn: LossFn,
    ds: SensorDataset,
    acfg: AsyncFLConfig,
) -> tuple[Params, AsyncEventMetrics]:
    """Simulate ``acfg.n_events`` fog ticks; returns (final params,
    per-tick metrics stacked along the leading axis)."""
    state = init_state(key, init_params, acfg)
    event_fn = make_event_fn(loss_fn, ds, acfg)
    final, metrics = jax.lax.scan(event_fn, state, None, length=acfg.n_events)
    return final.params, metrics
