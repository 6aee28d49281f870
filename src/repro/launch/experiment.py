"""Shared federated-experiment runner (one call = one paper table cell).

Every benchmark module and the training launcher funnel through
:func:`run_method`, so the evaluation protocol (train -> calibrate on
normal-only validation -> score test -> F1 / PA-F1, plus the per-round
energy/participation traces) is identical everywhere.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import anomaly, async_fl, flat_fl, hfl
from repro.core import cooperation as coop
from repro.core import topology as topo
from repro.data.synthetic import SensorDataset
from repro.models import autoencoder as ae
from repro.models.detector import Detector, choose

METHODS = (
    "centralised",
    "fedavg",
    "fedprox",
    "fedadam",
    "scaffold",
    "hfl-nocoop",
    "hfl-selective",
    "hfl-nearest",
    "hfl-adam",
    "hfl-async",
)

_RULES = {
    "hfl-nocoop": coop.CoopRule.NOCOOP,
    "hfl-selective": coop.CoopRule.SELECTIVE,
    "hfl-nearest": coop.CoopRule.NEAREST,
    "hfl-adam": coop.CoopRule.SELECTIVE,   # FedAdam server + selective coop
}

# FedProx proximal coefficient (paper uses mu ~ 0.01 scale defaults).
PROX_MU = 0.01
# Windows a window detector scores at once in evaluation.
EVAL_WINDOWS = 64


@dataclasses.dataclass(frozen=True)
class ExperimentResult:
    method: str
    f1: float
    precision: float
    recall: float
    participation: float       # mean over rounds
    e_total: float             # sum over rounds (J)
    e_s2f: float
    e_f2f: float
    e_f2g: float
    losses: tuple[float, ...]  # per-round mean training loss
    coop_links: float          # mean active fog-to-fog exchanges per round


def _detector_eval(
    det: Detector, params: Any, ds: SensorDataset, percentile: float,
    point_adjusted: bool,
) -> anomaly.F1Result:
    """Paper protocol with the GLOBAL threshold variant (Sec. V-D): tau is
    the ``percentile`` of the validation scores (Eq. 32).  A row detector
    scores every row; a window detector scores each sensor's validation
    and test series in non-overlapping windows of its length (a remainder
    shorter than a window is left out), one window batch at a time."""
    if det.window is None:
        d = ds.val.shape[-1]
        val = ds.val.reshape(-1, d)
        test = ds.test.reshape(-1, d)
        label = ds.test_label.reshape(-1)
        return anomaly.evaluate_scores(
            det.score, params, val, test, label,
            percentile=percentile, point_adjusted=point_adjusted,
        )
    length = det.window

    def windows(x):
        n_win = x.shape[1] // length
        return x[:, :n_win * length].reshape(-1, length, *x.shape[2:])

    def scores(p, w):
        return jax.lax.map(
            lambda one: det.score(p, one[None])[0], w, batch_size=EVAL_WINDOWS
        ).reshape(-1)

    return anomaly.evaluate_scores(
        scores, params, windows(ds.val), windows(ds.test),
        windows(ds.test_label).reshape(-1),
        percentile=percentile, point_adjusted=point_adjusted,
    )


def trial_metrics(
    method: str,
    key: jax.Array,
    ds: SensorDataset,
    cfg: hfl.HFLConfig | async_fl.AsyncFLConfig,
    *,
    percentile: float = 99.0,
    point_adjusted: bool = False,
    hidden: tuple[int, ...] | None = None,
    detector: Detector | None = None,
    client_mesh=None,
    return_params: bool = False,
) -> dict[str, jax.Array]:
    """One fully traced trial: train ``method`` from ``key``, evaluate.

    This is the jittable core shared by the sequential :func:`run_method`
    path and the batched :class:`repro.engine.Engine` (which vmaps it over
    a leading trial axis).  Everything returned is a jnp value; only
    ``method``/``cfg``/keyword knobs are static.

    ``client_mesh``: optional 1-D ``("data",)`` mesh — shards the client
    axis of the hfl / flat-FL round loops over devices (scaffold and the
    centralised oracle run unsharded; they bypass the fused pipeline).

    ``return_params``: include the trained model under ``"params"`` (used
    by ``Engine.run(store=...)`` to publish rounds for the serving path).

    ``detector``: the model (``models/detector``); None is the paper
    autoencoder with ``hidden`` widths (default (16, 8, 16)); not both.
    A window detector (the Anomaly Transformer) trains only in the
    hierarchical families, and reports its extra per-round stats
    (``assdis``) beside the losses.

    ``method="hfl-async"`` runs the event-driven staleness-aware family
    (``core/async_fl``); ``cfg`` may then be an
    :class:`repro.core.async_fl.AsyncFLConfig` (a plain ``HFLConfig`` is
    wrapped with the async defaults).  Every branch also reports
    ``sim_time_s`` — summed Eq. 21 round latency for the synchronous
    loops, the final simulated clock for the async loop — so
    accuracy-vs-simulated-wall-clock comparisons read one key.
    """
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; one of {METHODS}")
    det = choose(detector, hidden)
    if det.window is not None and method not in _RULES:
        raise ValueError(
            f"window detector {det.name!r} trains only in the hierarchical "
            f"families {tuple(_RULES)}, not {method!r}"
        )
    k_init, k_train = jax.random.split(key)
    dim = ds.train.shape[-1]
    params0 = det.init(k_init, dim)

    zero = jnp.zeros(())
    if method == "centralised":
        params, losses, e_up = flat_fl.train_centralised(
            k_train, params0, ae.loss, ds, cfg
        )
        # Oracle sees everything by construction.
        out = {
            "e_s2f": zero, "e_f2f": zero, "e_f2g": zero,
            "e_total": e_up, "participation": jnp.ones(()),
            "coop_links": zero, "losses": losses, "sim_time_s": zero,
            # No federated uplinks: the robustness counters are trivially 0.
            "nonfinite_total": zero, "erased_total": zero,
            "nonfinite_rounds": zero,
        }
    elif method == "hfl-async":
        acfg = (
            cfg if isinstance(cfg, async_fl.AsyncFLConfig)
            else async_fl.AsyncFLConfig(base=cfg)
        )
        params, m = async_fl.train(k_train, params0, ae.loss, ds, acfg)
        arrived_f = m.n_arrived.astype(jnp.float32)
        out = {
            "e_total": jnp.sum(m.e_total),
            "e_s2f": jnp.sum(m.e_s2f),
            "e_f2f": jnp.sum(m.e_f2f),
            "e_f2g": jnp.sum(m.e_f2g),
            "participation": jnp.mean(m.participation),
            "coop_links": jnp.mean(m.coop_links.astype(jnp.float32)),
            "losses": m.loss,
            "sim_time_s": m.t_sim[-1],
            "merges": jnp.sum(m.merged.astype(jnp.float32)),
            "staleness": jnp.sum(m.staleness * arrived_f)
            / jnp.maximum(jnp.sum(arrived_f), 1.0),
            "nonfinite_total": jnp.sum(m.n_nonfinite.astype(jnp.float32)),
            "erased_total": jnp.sum(m.n_erased.astype(jnp.float32)),
            "nonfinite_rounds": jnp.sum(
                1.0 - m.global_finite.astype(jnp.float32)
            ),
        }
    else:
        if method in ("fedavg", "fedprox", "fedadam"):
            run_cfg = cfg.replace(
                prox_mu=PROX_MU if method == "fedprox" else 0.0,
                server_opt="adam" if method == "fedadam" else cfg.server_opt,
            )
            params, m = flat_fl.train_flat(
                k_train, params0, ae.loss, ds, run_cfg,
                client_mesh=client_mesh,
            )
        elif method == "scaffold":
            params, m = flat_fl.train_scaffold(k_train, params0, ae.loss, ds, cfg)
        else:
            run_cfg = cfg.replace(
                rule=_RULES[method],
                prox_mu=0.0,
                server_opt="adam" if method == "hfl-adam" else cfg.server_opt,
            )
            params, m = hfl.train(
                k_train, params0, det, ds, run_cfg,
                client_mesh=client_mesh,
            )
        out = {
            "e_total": jnp.sum(m.e_total),
            "e_s2f": jnp.sum(m.e_s2f),
            "e_f2f": jnp.sum(m.e_f2f),
            "e_f2g": jnp.sum(m.e_f2g),
            "participation": jnp.mean(m.participation),
            "coop_links": jnp.mean(m.coop_links.astype(jnp.float32)),
            "losses": m.loss,
            "sim_time_s": jnp.sum(m.latency_s),
            "nonfinite_total": jnp.sum(m.n_nonfinite.astype(jnp.float32)),
            "erased_total": jnp.sum(m.n_erased.astype(jnp.float32)),
            "nonfinite_rounds": jnp.sum(
                1.0 - m.global_finite.astype(jnp.float32)
            ),
            **getattr(m, "detector_stats", {}),
        }

    with jax.named_scope("eval.detector"):
        f1 = _detector_eval(det, params, ds, percentile, point_adjusted)
    out.update(f1=f1.f1, precision=f1.precision, recall=f1.recall)
    if return_params:
        out["params"] = params
    return out


def run_method(
    method: str,
    ds: SensorDataset,
    cfg: hfl.HFLConfig | async_fl.AsyncFLConfig,
    seed: int = 0,
    percentile: float = 99.0,
    point_adjusted: bool = False,
    hidden: tuple[int, ...] = (16, 8, 16),
) -> ExperimentResult:
    """Train ``method`` on ``ds`` and evaluate the paper's metrics."""
    m = trial_metrics(
        method, jax.random.key(seed), ds, cfg,
        percentile=percentile, point_adjusted=point_adjusted, hidden=hidden,
    )
    return ExperimentResult(
        method=method,
        f1=float(m["f1"]),
        precision=float(m["precision"]),
        recall=float(m["recall"]),
        losses=tuple(float(x) for x in m["losses"]),
        participation=float(m["participation"]),
        e_total=float(m["e_total"]),
        e_s2f=float(m["e_s2f"]),
        e_f2f=float(m["e_f2f"]),
        e_f2g=float(m["e_f2g"]),
        coop_links=float(m["coop_links"]),
    )


def audit_trial(
    method: str,
    key: jax.Array,
    cfg: hfl.HFLConfig,
    d: int = 1352,
    l_u: jax.Array | float | None = None,
) -> dict[str, jax.Array]:
    """One fully traced training-free audit trial (see :func:`audit_method`).

    Jittable core shared by the sequential wrapper and the batched engine:
    samples a deployment from ``key``, replays Algorithm 1's association /
    cooperation / energy accounting over ``cfg.rounds`` rounds, and returns
    summed energies + mean participation as jnp scalars.

    ``l_u`` overrides the uplink payload (bits).  The audit touches the
    compressor ONLY through this number, so ``Engine.sweep`` precomputes it
    per config and feeds it as a swept operand — audit cells that differ
    only in compressor settings then share one compiled program.
    """
    from repro.core import association as assoc
    from repro.core import compression as comp
    from repro.core import cooperation as coop_m
    from repro.core import energy as en
    from repro.core import topology as topo_m

    if method in ("fedavg", "fedprox", "fedadam", "scaffold"):
        kind = "flat"
    elif method in _RULES:
        kind = "hfl"
    else:
        raise ValueError(f"audit unsupported for {method!r}")

    dep0 = topo_m.sample_deployment(key, cfg.deployment)
    if l_u is None:
        l_u = comp.payload_bits(d, cfg.compressor)
    l_full = 32.0 * d

    def round_fn(carry, k):
        dep = carry
        dep = topo_m.gauss_markov_step(k, dep, cfg.deployment) if cfg.fog_mobility else dep
        if kind == "flat":
            fa = assoc.flat_association(dep, cfg.channel)
            e_up = en.tx_energy_j(l_u, fa.dist_m, cfg.channel, cfg.energy)
            e_s2f = jnp.sum(jnp.where(fa.participates, e_up, 0.0))
            out = dict(
                e_s2f=e_s2f, e_f2f=jnp.zeros(()), e_f2g=jnp.zeros(()),
                participation=jnp.mean(fa.participates.astype(jnp.float32)),
                coop_links=jnp.zeros(()),
            )
        else:
            fa = assoc.nearest_feasible_fog(dep, cfg.channel)
            decision = coop_m.decide(
                _RULES[method], dep.fog_pos, fa.cluster_size, cfg.channel
            )
            e_up = en.tx_energy_j(l_u, fa.dist_m, cfg.channel, cfg.energy)
            e_s2f = jnp.sum(jnp.where(fa.participates, e_up, 0.0))
            fog_active = fa.cluster_size > 0
            e_ff = en.tx_energy_j(
                l_full, decision.dist_m, cfg.channel, cfg.energy
            )
            e_f2f = jnp.sum(
                jnp.where(decision.cooperates & fog_active, e_ff, 0.0)
            )
            e_fg = en.tx_energy_j(
                l_full, fa.fog_gateway_dist_m, cfg.channel, cfg.energy
            )
            e_f2g = jnp.sum(
                jnp.where(fog_active & fa.fog_gateway_feasible, e_fg, 0.0)
            )
            out = dict(
                e_s2f=e_s2f, e_f2f=e_f2f, e_f2g=e_f2g,
                participation=jnp.mean(fa.participates.astype(jnp.float32)),
                coop_links=jnp.sum(decision.cooperates.astype(jnp.float32)),
            )
        return dep, out

    keys = jax.random.split(jax.random.fold_in(key, 1), cfg.rounds)
    _, m = jax.lax.scan(round_fn, dep0, keys)
    total = {k: jnp.sum(v) for k, v in m.items() if k.startswith("e_")}
    total["e_total"] = total["e_s2f"] + total["e_f2f"] + total["e_f2g"]
    total["participation"] = jnp.mean(m["participation"])
    total["coop_links"] = jnp.mean(m["coop_links"])
    return total


def audit_method(
    method: str,
    cfg: hfl.HFLConfig,
    d: int = 1352,
    seed: int = 0,
) -> dict:
    """Replay Algorithm 1's decision + energy accounting WITHOUT training.

    Per-round communication energy in the simulator depends only on the
    topology, association/cooperation decisions, and payload sizes — not on
    model values — so the paper's *energy and participation* tables can be
    reproduced at full scale (N=200, T=20) cheaply.  F1 columns still come
    from :func:`run_method` at whatever scale the budget allows.
    """
    m = audit_trial(method, jax.random.key(seed), cfg, d)
    out = {k: float(v) for k, v in m.items()}
    out["method"] = method
    return out


def make_config(
    n_sensors: int,
    n_fog: int,
    rounds: int,
    **overrides: Any,
) -> hfl.HFLConfig:
    """Paper Table II defaults with per-experiment overrides."""
    dep = topo.DeploymentParams(n_sensors=n_sensors, n_fog=n_fog)
    return hfl.HFLConfig(deployment=dep, rounds=rounds).replace(**overrides)


def seed_sweep(
    method: str,
    ds_fn,
    cfg: hfl.HFLConfig,
    seeds: tuple[int, ...] = (0, 1, 2),
    **kw: Any,
) -> tuple[ExperimentResult, ...]:
    """Run ``method`` over seeds; ``ds_fn(seed) -> SensorDataset``."""
    return tuple(
        run_method(method, ds_fn(s), cfg, seed=s, **kw) for s in seeds
    )


def mean_std(values: list[float]) -> tuple[float, float]:
    arr = jnp.asarray(values)
    return float(jnp.mean(arr)), float(jnp.std(arr))
