"""Batched multi-deployment simulation engine.

One :class:`Engine` call evaluates a whole ablation cell — every seed and
deployment realisation of one configuration — as a single compiled XLA
program, instead of re-tracing ``hfl.train`` / ``flat_fl.train_*`` once
per seed the way the sequential path does.

Batch axes
----------
``Engine.run`` / ``Engine.audit`` take ``seeds`` (length S) and
``n_deployments`` (P) and build an (S, P) grid of trial keys:

* trial ``(s, 0)`` uses ``jax.random.key(seeds[s])`` — bit-identical to a
  sequential ``experiment.run_method(..., seed=seeds[s])`` call, which is
  what the equivalence tests in ``tests/test_engine.py`` pin down;
* trial ``(s, j>0)`` folds the deployment index into the seed key, giving
  an independent deployment realisation (and model init) per column.

The jittable per-trial functions from :mod:`repro.launch.experiment`
(``trial_metrics`` / ``audit_trial``) are nested-``vmap``-ped over the
grid — the inner deployment axis broadcasts each seed's dataset instead
of duplicating it on device — and the whole thing, the ``lax.scan`` over
rounds included, is jitted once per distinct (method, resolved config,
S, P, data shapes) cell.  Results come back with leading (S, P) axes.

Compressor default
------------------
Unless constructed with ``compressor="keep"``, the engine rewrites sparse
(``rho_s < 1``) ``mode="global"`` compressor configs to the blockwise
kernel path: compiled Pallas on TPU, the pure-jnp oracle (``kernels/ref``)
everywhere else — compiled Pallas needs a real TPU and interpret mode is
only a correctness tool, so CPU/GPU fall back automatically.
``Engine.resolve_config`` exposes the rewrite so sequential comparisons
can run the identical numerics.

Inside the round loops, a blockwise compressor and fog aggregation run
FUSED: ``core/aggregation.compress_and_aggregate`` dispatches to the
one-HBM-pass compress-and-aggregate kernel (``kernels/fused_agg``, jnp
oracle ``kernels/ref.compress_aggregate_ref``) which accumulates each
client's reconstruction straight into the (n_fog, d) fog buffers instead
of materialising dense (N, d) reconstructions and re-reading them in a
segment-sum.

Detectors
---------
``Engine(detector=...)`` picks the model every cell trains and evaluates
(``models/detector``); the default is the paper autoencoder at
``hidden`` widths (one argument or the other, not both).  The detector
also picks the client phase: a ``fusable`` one (the paper autoencoder)
trains in the fused local-train kernel, any other on the scan path.
``models/anomaly_transformer.detector`` trains the Anomaly Transformer on
stride-1 windows inside the hierarchical round's client-chunk scan and
scores non-overlapping windows.

Sharding
--------
With more than one device, input leaves are placed with the
``launch/sharding.py`` resolution rules on a 1-D ``("data",)`` mesh: the
trial axis shards when divisible by the device count, otherwise the
client axis of the dataset leaves does.  On one device this is a no-op.

``Engine(shard_clients=True)`` instead shards the CLIENT axis *inside*
the round loop: local SGD + fused compression run per-shard under
``shard_map`` on the ``launch/sharding.client_mesh()`` 1-D ``("data",)``
mesh, and the fog buffers are reduced with psum collectives
(``aggregation.hierarchical_mean``-style) — the multi-host lever for
deployments too large for a single device's memory.  It applies to the
hfl / flat-FL families; a sensor count the device count does not divide
raises, and the other families run the default placement.

Benchmarks
----------
``benchmarks/{ablations,table3_scalability,fig4_convergence,fig7_noniid}``
run every cell through a shared engine (``benchmarks.common.get_engine``)
and record ``Engine.take_log()`` — per-cell wall clock + whether the cell
hit the program cache — into their JSON under ``"engine"``, so compile
counts and wall-clock are tracked from PR 1 onward.  CI smoke-runs the
kernel microbenchmark; the tier-1 suite covers batched-vs-sequential
equivalence and Pallas-vs-ref parity.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import async_fl, hfl
from repro.core import compression as comp
from repro.core import drift as drf
from repro.core import faults as flt
from repro.data.synthetic import SensorDataset
from repro.kernels import ops as kops
from repro.kernels.ops import default_use_pallas
from repro.launch import experiment as exp
from repro.launch import sharding as shard_rules
from repro.models import autoencoder as ae
from repro.models.detector import Detector, choose
from repro.optim.sgd import LocalTrainConfig


# Methods whose client phase bypasses ``optim/sgd.make_client_solver``.
_UNFUSED_CLIENT_PHASE = ("centralised", "scaffold")


def _base_cfg(cfg) -> hfl.HFLConfig:
    """The nested ``HFLConfig`` of an async config, else the config itself —
    every engine path that reads kernel/compressor/round statics goes
    through here so the four families share one code path."""
    return cfg.base if isinstance(cfg, async_fl.AsyncFLConfig) else cfg


def _cfg_key(cfg) -> tuple:
    """Hashable program-cache fingerprint of a (possibly array-bearing)
    config.  Dataclass configs hash fine while every leaf is a Python
    scalar, but leaves like ``AsyncFLConfig.arrival_delay_s`` may carry a
    trace-replay ARRAY — unhashable, and (because ``Engine.run`` closes
    over the config, baking leaves in as compile-time constants) the key
    must distinguish array CONTENT, not just shape.  Arrays become
    (shape, dtype, digest) triples; everything else passes through."""
    leaves, treedef = jax.tree_util.tree_flatten(cfg)
    out = []
    for x in leaves:
        if isinstance(x, (jax.Array, np.ndarray)):
            arr = np.asarray(x)
            out.append(("arr", arr.shape, str(arr.dtype),
                        hashlib.sha1(arr.tobytes()).hexdigest()))
        else:
            out.append(x)
    return (treedef, tuple(out))


def _describe_compressor(cc: comp.CompressorConfig) -> str:
    """Short human tag recorded per cell so bench JSONs show which
    numerics actually ran (the engine may rewrite ``global`` configs)."""
    if not cc.enabled:
        return "dense"
    backend = (
        ("pallas" if not cc.interpret else "pallas-interpret")
        if cc.use_pallas else "ref"
    ) if cc.mode == "blockwise" else "jnp"
    return f"{cc.mode}[{backend}] rho={cc.rho_s:g} q{cc.quant_bits}"


@dataclasses.dataclass(frozen=True)
class EngineRun:
    """Result of one batched cell.  Metric leaves have leading (S, P)."""

    method: str
    cfg: hfl.HFLConfig
    seeds: tuple[int, ...]
    n_deployments: int
    metrics: dict[str, jax.Array]
    wall_s: float
    fresh_compile: bool

    def __getitem__(self, name: str) -> jax.Array:
        return self.metrics[name]

    @property
    def f1(self) -> jax.Array:
        return self.metrics["f1"]

    @property
    def losses(self) -> jax.Array:
        """(S, P, T) per-round mean training loss."""
        return self.metrics["losses"]

    def seed_mean_std(self, name: str) -> tuple[float, float]:
        """Mean/std of a scalar metric over all (seed, deployment) trials."""
        v = jnp.asarray(self.metrics[name], jnp.float32)
        return float(jnp.mean(v)), float(jnp.std(v))


@dataclasses.dataclass(frozen=True)
class SweepRun:
    """Result of one config-axis sweep.  Metric leaves have leading
    (C, S, P) — config cell x seed x deployment."""

    method: str
    cfgs: tuple[hfl.HFLConfig, ...]   # resolved configs, input order
    seeds: tuple[int, ...]
    n_deployments: int
    metrics: dict[str, jax.Array]
    classes: tuple[dict, ...]         # per-shape-class execution info
    wall_s: float

    def __getitem__(self, name: str) -> jax.Array:
        return self.metrics[name]

    @property
    def compiled_programs(self) -> int:
        """Programs compiled fresh for THIS sweep (cache hits excluded)."""
        return sum(1 for c in self.classes if c["fresh_compile"])

    @property
    def n_classes(self) -> int:
        return len(self.classes)

    def cell(self, i: int) -> dict[str, jax.Array]:
        """Metrics of config cell ``i`` with the (S, P) trial axes kept."""
        return {k: v[i] for k, v in self.metrics.items()}

    def seed_mean_std(self, name: str, i: int) -> tuple[float, float]:
        v = jnp.asarray(self.metrics[name][i], jnp.float32)
        return float(jnp.mean(v)), float(jnp.std(v))


class Engine:
    """Unified batched front-end for the four round-loop families.

    * ``run``   — the trainable families: flat FL (``core/flat_fl``:
      fedavg/fedprox/fedadam/scaffold/centralised), hierarchical FL
      (``core/hfl``: the hfl-* cooperation rules), and the event-driven
      asynchronous family (``core/async_fl``: method ``"hfl-async"`` with
      an :class:`repro.core.async_fl.AsyncFLConfig` — its staleness knobs
      ``alpha`` / ``buffer_k`` / ``fog_k`` / timeouts are swept leaves,
      so ``sweep`` grids them exactly like the physics knobs);
    * ``sweep`` — ``run``/``audit`` over a whole CONFIG GRID: cells are
      grouped into shape-classes (identical static structure — enums,
      shapes, backend flags), each class's swept knobs (channel/energy
      physics, ``rho_s``, ``lr``, ...) are stacked along a new leading
      config axis, and one compiled program evaluates the whole class as
      a ``(C, S, P)`` grid;
    * ``audit`` — the training-free energy/participation replay of either
      family at paper scale;
    * ``pod_train_step`` — the TPU-mesh family (``core/mesh_fl``), returned
      as a cached jitted step for callers that own the mesh/batch loop.
    """

    def __init__(
        self,
        *,
        compressor: str = "auto",
        shard_trials: bool = True,
        shard_clients: bool = False,
        client_chunk: int | None = None,
        hidden: tuple[int, ...] | None = None,
        detector: Detector | None = None,
        percentile: float = 99.0,
        point_adjusted: bool = False,
    ) -> None:
        if compressor not in ("auto", "keep"):
            raise ValueError(f"compressor must be auto|keep, got {compressor!r}")
        if client_chunk is not None and (
            not isinstance(client_chunk, int) or client_chunk < 1
        ):
            raise ValueError(
                f"client_chunk must be None or a positive int, got "
                f"{client_chunk!r}"
            )
        self.compressor = compressor
        self.shard_trials = shard_trials
        self.shard_clients = shard_clients
        self.client_chunk = client_chunk
        # The model every cell trains: the paper AE at ``hidden`` widths
        # unless a detector is given (``models/detector``), never both.
        self.detector = choose(detector, hidden)
        self.hidden = (16, 8, 16) if hidden is None else hidden
        self.percentile = percentile
        self.point_adjusted = point_adjusted
        self._programs: dict[Any, Callable] = {}
        # Tiles per grid step of each program's compress kernels, as its
        # tracing call chose them (``engine.compress_tiles_per_step``).
        self._compress_tiles: dict[Any, list[int]] = {}
        self._last_calls: list[tuple[Callable, tuple]] = []
        self.compile_count = 0
        self.call_log: list[dict] = []

    # ------------------------------------------------------------------
    # config / data resolution
    # ------------------------------------------------------------------

    def resolve_compressor(self, cc: comp.CompressorConfig) -> comp.CompressorConfig:
        """The engine's compressor default: blockwise kernels, Pallas on TPU."""
        if self.compressor == "keep" or not cc.enabled or cc.rho_s >= 1.0:
            return cc
        if cc.quant_bits != 8 and cc.quant_bits < 32:
            return cc  # kernels are int8-only; keep paper global numerics
        use_pallas = default_use_pallas()
        if (cc.mode == "blockwise" and cc.use_pallas == use_pallas
                and cc.interpret == (not use_pallas)):
            return cc
        return cc.replace(
            mode="blockwise",
            use_pallas=use_pallas,
            interpret=not use_pallas,
        )

    def resolve_local_solver(
        self, ls: LocalTrainConfig
    ) -> LocalTrainConfig:
        """The engine's local-train backend: Pallas on TPU, the
        ``kernels/ref`` oracle elsewhere."""
        use_pallas = default_use_pallas()
        if ls.use_pallas == use_pallas and ls.interpret == (not use_pallas):
            return ls
        return ls.replace(use_pallas=use_pallas, interpret=not use_pallas)

    def resolve_config(self, cfg):
        """Apply the engine's kernel-backend defaults; an async config
        resolves through its nested ``base`` round-loop config.

        ``Engine(client_chunk=...)`` stamps the fleet-axis chunk size into
        configs that leave it unset (``cfg.client_chunk is None``); an
        explicit per-config value always wins.  The knob is static aux
        (shape-bearing), so differing chunk settings split sweep
        shape-classes — which is why :meth:`_audit_normal` blanks it.
        """
        if isinstance(cfg, async_fl.AsyncFLConfig):
            return cfg.replace(base=self.resolve_config(cfg.base))
        kw: dict[str, Any] = dict(
            compressor=self.resolve_compressor(cfg.compressor),
            local_solver=self.resolve_local_solver(cfg.local_solver),
        )
        if cfg.client_chunk is None and self.client_chunk is not None:
            kw["client_chunk"] = self.client_chunk
        return cfg.replace(**kw)

    @staticmethod
    def stack_datasets(ds_list: Sequence[SensorDataset]) -> SensorDataset:
        """Stack per-seed datasets along a new leading trial axis."""
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ds_list)

    def _as_stacked(self, ds, seeds: Sequence[int]) -> SensorDataset:
        if callable(ds):
            return self.stack_datasets([ds(s) for s in seeds])
        if ds.train.ndim == 3:  # one dataset shared by every seed
            return self.stack_datasets([ds] * len(seeds))
        if ds.train.shape[0] != len(seeds):
            raise ValueError(
                f"stacked dataset has {ds.train.shape[0]} entries for "
                f"{len(seeds)} seeds"
            )
        return ds

    @staticmethod
    def _trial_keys(seeds: Sequence[int], n_deployments: int) -> jax.Array:
        """(S, P) trial keys; column 0 is exactly ``jax.random.key(seed)``."""
        if not seeds or n_deployments < 1:
            raise ValueError(
                f"need >=1 seed and n_deployments >= 1, got "
                f"{len(seeds)} seed(s), n_deployments={n_deployments}"
            )
        rows = []
        for s in seeds:
            base = jax.random.key(s)
            rows.append(jnp.stack([
                base if j == 0 else jax.random.fold_in(base, j)
                for j in range(n_deployments)
            ]))
        return jnp.stack(rows)

    # ------------------------------------------------------------------
    # program cache / sharding / instrumentation
    # ------------------------------------------------------------------

    def _get_program(self, cache_key: Any, build: Callable[[], Callable]):
        fn = self._programs.get(cache_key)
        fresh = fn is None
        if fresh:
            fn = jax.jit(build())
            self._programs[cache_key] = fn
            self.compile_count += 1
        return fn, fresh

    def _client_mesh(self, method: str, stacked: SensorDataset):
        """The in-loop client-axis mesh for a ``run`` cell, or None.

        Client sharding needs >1 device and a round-loop family that
        routes through the fused pipeline (hfl / flat FL); other cells keep
        default placement.  A sensor count the device count does not
        divide is an error, not a silent drop to one device.
        """
        if not self.shard_clients or method in (
            *_UNFUSED_CLIENT_PHASE, "hfl-async"
        ):
            return None
        devices = jax.devices()
        if len(devices) <= 1:
            return None
        n_clients = stacked.train.shape[1]
        if n_clients % len(devices) != 0:
            raise ValueError(
                f"shard_clients needs the sensor count ({n_clients}) to be "
                f"a multiple of the device count ({len(devices)})"
            )
        return shard_rules.client_mesh(devices)

    def _place(self, tree: Any, n_leading: int) -> Any:
        """Shard inputs over devices with the launch/sharding rules.

        Prefers the leading (seed) axis; falls back to the client axis of
        dataset leaves when the seed count does not divide the device
        count.  Single-device: identity.
        """
        devices = jax.devices()
        if not self.shard_trials or len(devices) <= 1:
            return tree
        import numpy as np

        # resolve_spec expects the production ("data", "model") axis pair;
        # a trivial model axis keeps trials pure data-parallel.
        mesh = jax.sharding.Mesh(
            np.asarray(devices).reshape(-1, 1), ("data", "model")
        )
        trial_ok = n_leading % len(devices) == 0

        def place(x):
            if not hasattr(x, "ndim") or x.ndim == 0:
                return x
            if trial_ok:
                logical = ("batch",) + (None,) * (x.ndim - 1)
            elif x.ndim >= 2:
                logical = (None, "batch") + (None,) * (x.ndim - 2)
            else:
                return x
            spec = shard_rules.resolve_spec(logical, x.shape, mesh)
            return jax.device_put(x, jax.sharding.NamedSharding(mesh, spec))

        return jax.tree_util.tree_map(place, tree)

    def _timed_call(self, fn, *args, append: bool = False):
        """Run ``fn`` to completion; remember it for :meth:`compiled`
        (``append``: one more program of the same call, a sweep class)."""
        self._last_calls = (self._last_calls if append else []) + [(fn, args)]
        with telemetry.span("engine.execute") as call:
            out = fn(*args)
            out = jax.tree_util.tree_map(jax.block_until_ready, out)
        return out, call.seconds

    def _log(self, **entry) -> None:
        self.call_log.append(entry)

    def compiled(self) -> list[jax.stages.Compiled]:
        """The programs the latest ``run`` / ``sweep`` (one per shape-class)
        / ``audit`` / ``score`` call executed, as compiled for their
        devices — free, the jit cache holds them.  ``as_text()`` shows a
        Pallas kernel as a ``tpu_custom_call`` named after its wrapper and
        a client-sharded cell's fog psum as an ``all-reduce``;
        ``input_shardings`` shows how the inputs were laid out."""
        return [fn.lower(*args).compile() for fn, args in self._last_calls]

    def take_log(self) -> list[dict]:
        """Drain the per-call log (benchmarks snapshot this into JSON)."""
        entries, self.call_log = self.call_log, []
        return entries

    # ------------------------------------------------------------------
    # the three families
    # ------------------------------------------------------------------

    def run(
        self,
        method: str,
        cfg: hfl.HFLConfig,
        seeds: Sequence[int],
        ds: SensorDataset | Callable[[int], SensorDataset],
        *,
        n_deployments: int = 1,
        label: str | None = None,
        store: Any | None = None,
        publish_step: int | None = None,
    ) -> EngineRun:
        """Train + evaluate ``method`` for every (seed, deployment) trial.

        ``ds``: a per-seed callable, a single dataset (shared), or a
        dataset stacked along a leading ``len(seeds)`` axis.

        ``store``: optional ``checkpoint.CheckpointStore`` — publishes the
        trained params of trial (seeds[0], deployment 0) as round
        ``publish_step`` (default ``cfg.rounds``), the hand-off point to
        the serving path (``serving/service.ScoringService``).
        """
        with telemetry.span("engine.prepare"):
            cfg = self.resolve_config(cfg)
            seeds = tuple(int(s) for s in seeds)
            stacked = self._as_stacked(ds, seeds)
            s_n, p_n = len(seeds), n_deployments
            base = _base_cfg(cfg)
            dim = stacked.train.shape[-1]
            det = self.detector
            if telemetry.recording():
                shapes = jax.eval_shape(lambda k: det.init(k, dim), jax.random.key(0))
                if (det.fusable and base.local_solver.use_pallas
                        and method not in _UNFUSED_CLIENT_PHASE):
                    telemetry.observe("engine.local_train_pack",
                                      kops.local_train_pack(ae.widths(shapes)))
                telemetry.observe("engine.detector_params", sum(
                    x.size for x in jax.tree_util.tree_leaves(shapes)))
                telemetry.observe("engine.local_windows", det.trained_per_round(
                    stacked.train.shape[-2], base.batch_size, base.local_epochs))
            keys = self._trial_keys(seeds, p_n)           # (S, P)
            client_mesh = self._client_mesh(method, stacked)
            return_params = store is not None
            shapes = tuple(
                (x.shape, str(x.dtype)) for x in jax.tree_util.tree_leaves(stacked)
            )
            cache_key = ("run", method, _cfg_key(cfg), s_n, p_n, shapes,
                         self.detector, self.percentile, self.point_adjusted,
                         client_mesh.size if client_mesh is not None else 0,
                         return_params)

            def build():
                def trial(key, one_ds):
                    return exp.trial_metrics(
                        method, key, one_ds, cfg,
                        percentile=self.percentile,
                        point_adjusted=self.point_adjusted,
                        detector=self.detector,
                        client_mesh=client_mesh,
                        return_params=return_params,
                    )

                # Inner vmap broadcasts the seed's dataset over the deployment
                # columns (no device-side duplication); outer vmap pairs each
                # seed with its dataset.  Output leaves lead with (S, P).
                return jax.vmap(jax.vmap(trial, in_axes=(0, None)))

            fn, fresh = self._get_program(cache_key, build)
            if client_mesh is None:
                keys, stacked = self._place(keys, s_n), self._place(stacked, s_n)
            else:
                # Sensor axis (axis 1 of every stacked leaf) over the client
                # mesh: the in-loop shard_map then reads local slices as-is.
                on_mesh = jax.sharding.NamedSharding(
                    client_mesh, jax.sharding.PartitionSpec(None, "data")
                )
                stacked = jax.device_put(stacked, on_mesh)
        with kops.compress_tiles_traced() as tiles:
            out, wall = self._timed_call(fn, keys, stacked)
        if tiles:
            self._compress_tiles[cache_key] = list(dict.fromkeys(tiles))
        if telemetry.recording() and cache_key in self._compress_tiles:
            telemetry.observe("engine.compress_tiles_per_step",
                              self._compress_tiles[cache_key])
        if store is not None:
            with telemetry.span("engine.publish"):
                params0 = jax.tree_util.tree_map(lambda a: a[0, 0], out.pop("params"))
                store.publish(
                    _base_cfg(cfg).rounds if publish_step is None else publish_step,
                    params0,
                )
        self._log(kind="run", method=method, label=label or method,
                  n_trials=s_n * p_n, wall_s=wall, fresh_compile=fresh,
                  compressor=_describe_compressor(_base_cfg(cfg).compressor),
                  client_sharded=client_mesh is not None)
        return EngineRun(method, cfg, seeds, p_n, out, wall, fresh)

    def audit(
        self,
        method: str,
        cfg: hfl.HFLConfig,
        seeds: Sequence[int],
        *,
        d: int = 1352,
        n_deployments: int = 1,
        label: str | None = None,
    ) -> dict[str, jax.Array]:
        """Batched training-free energy/participation audit.

        Returns summed energies / mean participation with (S, P) leading
        axes; trial (s, 0) matches ``experiment.audit_method(seed=s)``.
        """
        cfg = self.resolve_config(cfg)
        seeds = tuple(int(s) for s in seeds)
        s_n, p_n = len(seeds), n_deployments
        keys = self._trial_keys(seeds, p_n)           # (S, P)
        cache_key = ("audit", method, _cfg_key(cfg), s_n, p_n, d)

        def build():
            trial = lambda key: exp.audit_trial(method, key, cfg, d)  # noqa: E731
            return jax.vmap(jax.vmap(trial))

        fn, fresh = self._get_program(cache_key, build)
        out, wall = self._timed_call(fn, self._place(keys, s_n))
        self._log(kind="audit", method=method, label=label or method,
                  n_trials=s_n * p_n, wall_s=wall, fresh_compile=fresh,
                  compressor=_describe_compressor(cfg.compressor))
        return out

    # ------------------------------------------------------------------
    # config-axis sweeps
    # ------------------------------------------------------------------

    @staticmethod
    def stack_configs(cfgs: Sequence[hfl.HFLConfig]) -> hfl.HFLConfig:
        """Stack same-shape-class configs: every swept leaf becomes a
        (C,) f32 array, static aux fields come from the first config."""
        return jax.tree_util.tree_map(
            lambda *xs: jnp.stack([jnp.asarray(x, jnp.float32) for x in xs]),
            *cfgs,
        )

    @staticmethod
    def _audit_normal(cfg: hfl.HFLConfig) -> hfl.HFLConfig:
        """Blank out the static fields the audit family never reads.

        The audit touches the compressor only through the uplink payload
        size — which the sweep feeds as a swept operand — so cells that
        differ only in compressor/solver/server statics collapse into one
        shape-class.
        """
        if isinstance(cfg, async_fl.AsyncFLConfig):
            raise ValueError(
                "audit family is training-free and synchronous; it does "
                "not support AsyncFLConfig cells"
            )
        return cfg.replace(
            local_epochs=1,
            batch_size=32,
            server_opt="sgd",
            local_solver=LocalTrainConfig(),
            compressor=comp.CompressorConfig(),
            faults=flt.FaultConfig(),
            drift=drf.DriftConfig(),
            trim_frac=0.0,
            robust="mean",
            client_chunk=None,  # audits never run the client phase
        )

    @staticmethod
    def _kernel_static_knobs(cfg: hfl.HFLConfig) -> tuple:
        """Knobs the Pallas kernels bake into their bodies.

        On the jnp-oracle backend these trace (bisection selection, scalar
        arithmetic) and the sweep batches across their values; a
        pallas-backed config must keep them concrete, so they join the
        shape-class signature and are re-pinned inside the program.
        """
        base = _base_cfg(cfg)
        knobs = {}
        cc = base.compressor
        if cc.enabled and cc.is_sparse and cc.mode == "blockwise" and cc.use_pallas:
            knobs["rho_s"] = float(cc.rho_s)
        if base.local_solver.use_pallas:
            knobs["lr"] = float(base.lr)
            knobs["prox_mu"] = float(base.prox_mu)
        if base.robust != "mean" and cc.use_pallas:
            knobs["trim_frac"] = float(base.trim_frac)
        return tuple(sorted(knobs.items()))

    def _sweep_classes(
        self, cfgs: Sequence[hfl.HFLConfig], family: str,
        ds_shapes: Sequence[tuple] | None,
    ) -> tuple[list[hfl.HFLConfig], dict]:
        """Group sweep cells into shape-classes.

        The signature is the config's pytree STRUCTURE (every static aux
        field — rule enum, round/epoch counts, compressor mode/bits/flags,
        deployment geometry — lives in the treedef; swept leaves do not),
        plus any kernel-bound knobs and, for per-cell datasets, the data
        shapes.  Mixed enums/static shapes therefore never co-batch.
        """
        norm, groups = [], {}
        for i, rcfg in enumerate(cfgs):
            ncfg = self._audit_normal(rcfg) if family == "audit" else rcfg
            norm.append(ncfg)
            sig = (
                jax.tree_util.tree_structure(ncfg),
                self._kernel_static_knobs(rcfg) if family == "run" else (),
                ds_shapes[i] if ds_shapes is not None else None,
            )
            groups.setdefault(sig, []).append(i)
        return norm, groups

    def sweep(
        self,
        method: str | Sequence[str],
        cfgs: Sequence[hfl.HFLConfig],
        seeds: Sequence[int],
        ds: Any = None,
        *,
        n_deployments: int = 1,
        family: str = "run",
        d: int = 1352,
        label: str | None = None,
    ) -> SweepRun:
        """Evaluate a whole config grid: ONE compiled program per
        shape-class, each running its cells as a leading config axis on
        top of the (seed, deployment) trial grid.

        ``cfgs``: the hyperparameter cells.  Cells may differ in any
        traceable knob (``ChannelParams`` / ``EnergyParams`` physics,
        ``CompressorConfig.rho_s``, ``lr`` / ``prox_mu`` / ``server_lr`` /
        ``compute_rate_flops``) and still share a program; cells that
        differ in static structure — cooperation rule, round/epoch/batch
        counts, compressor mode/bit-width/backend, deployment geometry —
        split into separate shape-classes (and separate programs).

        ``family="run"`` trains and evaluates (``ds`` required: one
        dataset/callable shared by every cell, or a length-C sequence of
        per-cell datasets, each in any form ``Engine.run`` accepts);
        ``family="audit"`` replays the training-free energy accounting
        (``d`` = model size; ``ds`` ignored).

        ``method`` may be a length-C sequence for ``family="audit"``: the
        cells' methods become a ``lax.switch`` branch index — a swept
        operand like the payload size — so audit cells that differ ONLY in
        method (e.g. Table III's four methods at one N) share one compiled
        program instead of one per (cfg, method) pair.  The training
        family keeps one method per sweep (its per-method round loops
        differ structurally).

        Returns a :class:`SweepRun` with metric leaves shaped (C, S, P);
        cell ``i`` matches ``Engine.run(cfgs[i], ...)`` /
        ``Engine.audit`` to float tolerance.
        """
        if family not in ("run", "audit"):
            raise ValueError(f"family must be run|audit, got {family!r}")
        if not cfgs:
            raise ValueError("need at least one config cell")
        if isinstance(method, str):
            methods = (method,) * len(cfgs)
        else:
            methods = tuple(method)
            if len(methods) != len(cfgs):
                raise ValueError(
                    f"got {len(methods)} methods for {len(cfgs)} configs"
                )
            if family == "run" and len(set(methods)) > 1:
                raise ValueError(
                    "per-cell methods are audit-only (the training "
                    "family's round loops differ structurally per method)"
                )
        # Order-preserving unique methods — the lax.switch branch table.
        uniq = tuple(dict.fromkeys(methods))
        method_desc = uniq[0] if len(uniq) == 1 else "+".join(uniq)
        seeds = tuple(int(s) for s in seeds)
        s_n, p_n = len(seeds), n_deployments
        keys = self._trial_keys(seeds, p_n)           # (S, P)
        rcfgs = tuple(self.resolve_config(c) for c in cfgs)

        stacked_ds, ds_shapes = None, None
        if family == "run":
            if ds is None:
                raise ValueError("family='run' sweeps need a dataset")
            shape_of = lambda one: tuple(  # noqa: E731
                (x.shape, str(x.dtype))
                for x in jax.tree_util.tree_leaves(one)
            )
            # A SensorDataset is itself a (named) tuple: one shared dataset.
            if isinstance(ds, (list, tuple)) and not isinstance(
                ds, SensorDataset
            ):
                if len(ds) != len(rcfgs):
                    raise ValueError(
                        f"got {len(ds)} datasets for {len(rcfgs)} configs"
                    )
                stacked_ds = [self._as_stacked(one, seeds) for one in ds]
                ds_shapes = [shape_of(one) for one in stacked_ds]
            else:
                shared = self._as_stacked(ds, seeds)
                stacked_ds = [shared] * len(rcfgs)
                ds_shapes = [shape_of(shared)] * len(rcfgs)

        norm, groups = self._sweep_classes(rcfgs, family, ds_shapes)

        per_cfg: list[Any] = [None] * len(rcfgs)
        classes, wall_total = [], 0.0
        for ci, (sig, idxs) in enumerate(groups.items()):
            stacked_cfg = self.stack_configs([norm[i] for i in idxs])
            rep = rcfgs[idxs[0]]
            knobs = dict(self._kernel_static_knobs(rep))
            cache_key = ("sweep", family, uniq, sig, len(idxs), s_n, p_n,
                         d, self.detector, self.percentile, self.point_adjusted)

            if family == "run":
                shared_cell_ds = all(
                    stacked_ds[i] is stacked_ds[idxs[0]] for i in idxs
                )
                if shared_cell_ds:
                    ds_arg, ds_axis = stacked_ds[idxs[0]], None
                else:
                    ds_arg = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs),
                        *[stacked_ds[i] for i in idxs],
                    )
                    ds_axis = 0
                cache_key += (ds_axis,)

                def build(knobs=knobs, ds_axis=ds_axis):
                    def trial(cfg_, key, one_ds):
                        if knobs:
                            # kernel-bound knobs stay concrete per class;
                            # async cells carry them in the nested base.
                            b = _base_cfg(cfg_)
                            b = b.replace(
                                lr=knobs.get("lr", b.lr),
                                prox_mu=knobs.get("prox_mu", b.prox_mu),
                                trim_frac=knobs.get(
                                    "trim_frac", b.trim_frac
                                ),
                            )
                            if "rho_s" in knobs:
                                b = b.replace(
                                    compressor=b.compressor.replace(
                                        rho_s=knobs["rho_s"]
                                    )
                                )
                            cfg_ = (
                                cfg_.replace(base=b)
                                if isinstance(cfg_, async_fl.AsyncFLConfig)
                                else b
                            )
                        return exp.trial_metrics(
                            uniq[0], key, one_ds, cfg_,
                            percentile=self.percentile,
                            point_adjusted=self.point_adjusted,
                            detector=self.detector,
                        )

                    dep_v = jax.vmap(trial, in_axes=(None, 0, None))
                    seed_v = jax.vmap(dep_v, in_axes=(None, 0, 0))
                    return jax.vmap(seed_v, in_axes=(0, None, ds_axis))

                fn, fresh = self._get_program(cache_key, build)
                # Same launch/sharding placement rules as Engine.run:
                # per-cell datasets shard over the config axis, shared
                # ones over the seed axis (no-op on one device).
                placed_keys = self._place(keys, s_n)
                placed_ds = self._place(
                    ds_arg, len(idxs) if ds_axis == 0 else s_n
                )
                out, wall = self._timed_call(
                    fn, stacked_cfg, placed_keys, placed_ds, append=ci > 0
                )
            else:
                l_u = jnp.asarray(
                    [float(comp.payload_bits(d, rcfgs[i].compressor))
                     for i in idxs],
                    jnp.float32,
                )
                # Per-cell method as a traced branch index: the program
                # carries every unique method's audit as a lax.switch
                # branch, so cells differing only in method co-batch.
                midx = jnp.asarray(
                    [uniq.index(methods[i]) for i in idxs], jnp.int32
                )

                def build():
                    def trial(cfg_, lu, mi, key):
                        if len(uniq) == 1:
                            return exp.audit_trial(
                                uniq[0], key, cfg_, d, l_u=lu
                            )
                        branches = [
                            (lambda k_, c_, l_, m=m: exp.audit_trial(
                                m, k_, c_, d, l_u=l_
                            ))
                            for m in uniq
                        ]
                        return jax.lax.switch(mi, branches, key, cfg_, lu)

                    dep_v = jax.vmap(trial, in_axes=(None, None, None, 0))
                    seed_v = jax.vmap(dep_v, in_axes=(None, None, None, 0))
                    return jax.vmap(seed_v, in_axes=(0, 0, 0, None))

                fn, fresh = self._get_program(cache_key, build)
                out, wall = self._timed_call(
                    fn, stacked_cfg, l_u, midx, self._place(keys, s_n),
                    append=ci > 0,
                )

            for pos, i in enumerate(idxs):
                per_cfg[i] = jax.tree_util.tree_map(lambda a: a[pos], out)
            info = dict(
                indices=tuple(idxs), n_cells=len(idxs), wall_s=wall,
                fresh_compile=fresh,
                compressor=_describe_compressor(_base_cfg(rep).compressor),
            )
            classes.append(info)
            wall_total += wall
            self._log(kind=f"sweep-{family}", method=method_desc,
                      label=label or f"sweep:{method_desc}",
                      n_cells=len(idxs),
                      n_trials=len(idxs) * s_n * p_n, wall_s=wall,
                      fresh_compile=fresh, compressor=info["compressor"])

        # Stack per metric into (C, S, P, ...) where shapes agree across
        # classes; a metric whose trailing shape differs between classes
        # (e.g. per-round losses under different round counts) stays a
        # C-tuple — cell indexing works identically either way.
        metrics = {}
        for name in per_cfg[0]:
            vals = [m[name] for m in per_cfg]
            if len({v.shape for v in vals}) == 1:
                metrics[name] = jnp.stack(vals)
            else:
                metrics[name] = tuple(vals)
        return SweepRun(method_desc, rcfgs, seeds, p_n, metrics,
                        tuple(classes), wall_total)

    def reachability(
        self,
        cfg: hfl.HFLConfig,
        seeds: Sequence[int],
        *,
        n_deployments: int = 1,
        label: str | None = None,
    ) -> dict[str, jax.Array]:
        """Batched geometry-only reachability study (the Fig. 5 family).

        Training- and model-free: each trial samples a deployment and
        computes the direct-gateway / fog-assisted / fog-to-gateway
        feasibility fractions.  Returns (S, P)-leading arrays; trial
        (s, 0) matches a sequential ``topo.sample_deployment`` +
        ``participation.reachability`` call from ``jax.random.key(s)``.
        """
        from repro.core import participation as part
        from repro.core import topology as topo

        seeds = tuple(int(s) for s in seeds)
        s_n, p_n = len(seeds), n_deployments
        keys = self._trial_keys(seeds, p_n)           # (S, P)
        cache_key = ("reach", cfg.deployment, cfg.channel, s_n, p_n)

        def build():
            def trial(key):
                dep = topo.sample_deployment(key, cfg.deployment)
                r = part.reachability(dep, cfg.channel)
                return {
                    "direct_gateway": r.direct_gateway,
                    "fog_assisted": r.fog_assisted,
                    "fog_to_gateway": r.fog_to_gateway,
                }

            return jax.vmap(jax.vmap(trial))

        fn, fresh = self._get_program(cache_key, build)
        out, wall = self._timed_call(fn, keys)
        self._log(kind="reachability", method="reachability",
                  label=label or "reachability", n_trials=s_n * p_n,
                  wall_s=wall, fresh_compile=fresh, compressor="n/a")
        return out

    def score(
        self,
        params: Any,
        x: jax.Array,
        tau: jax.Array | float,
        *,
        n_trial_axes: int = 0,
        label: str | None = None,
    ):
        """Batched fused anomaly scoring — the serving family (ISSUE 3).

        ``x``: telemetry ``(..., d)``; the fused score kernel
        (``serving/score``: Pallas on TPU, jnp oracle elsewhere) flattens
        everything below the trial axes into one row sweep.  ``params``
        may carry ``n_trial_axes`` leading axes (e.g. the (S, P) grid of
        a training cell) which are vmapped exactly like ``run``; ``x`` and
        ``tau`` broadcast rows per trial.  With no trial axes the leading
        (fleet) axis of ``x`` shards over devices via the launch/sharding
        rules — the fleet-scale lever.  Returns a ``ScoreResult`` with
        leaves shaped ``x.shape[:-1]``.
        """
        # The serving package re-exports the function under the submodule's
        # name, so import the function itself.
        from repro.serving.score import score as serving_score_fn

        x = jnp.asarray(x)
        tau_b = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), x.shape[:-1])
        use_pallas = default_use_pallas()
        treedef = jax.tree_util.tree_structure(params)
        p_shapes = tuple(
            (tuple(leaf.shape), str(leaf.dtype))
            for leaf in jax.tree_util.tree_leaves(params)
        )
        cache_key = ("score", treedef, p_shapes, x.shape, str(x.dtype),
                     n_trial_axes)

        def build():
            def one(p, xx, tt):
                return serving_score_fn(
                    p, xx, tt, use_pallas=use_pallas,
                    interpret=not use_pallas,
                )

            fn = one
            for _ in range(n_trial_axes):
                fn = jax.vmap(fn)
            return fn

        fn, fresh = self._get_program(cache_key, build)
        n_leading = x.shape[0]
        placed = self._place((x, tau_b), n_leading)
        out, wall = self._timed_call(fn, params, *placed)
        n_rows = math.prod(x.shape[:-1])
        self._log(kind="score", method="score", label=label or "score",
                  n_trials=n_rows, wall_s=wall, fresh_compile=fresh,
                  compressor="n/a")
        return out

    def pod_train_step(
        self,
        model_cfg: Any,
        mesh: jax.sharding.Mesh | None = None,
        *,
        rho_s: float = 0.05,
        self_weight: float = 0.5,
        mode: str = "int8",
        local_epochs: int = 1,
    ) -> Callable:
        """Cached jitted TPU-mesh pod step (``core/mesh_fl`` family).

        Defaults to a single-pod host mesh so the same entry point works
        on CPU; pass the production mesh on real hardware.
        ``local_epochs > 1`` runs E local passes per pod through the
        shared ``optim/sgd`` local-training driver (delta exchange).
        """
        from repro.core import mesh_fl
        from repro.launch.mesh import make_mesh

        if mesh is None:
            mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
        cache_key = ("pod", repr(model_cfg), tuple(sorted(mesh.shape.items())),
                     rho_s, self_weight, mode, local_epochs)

        def build():
            return mesh_fl.make_pod_hfl_train_step(
                model_cfg, mesh, rho_s=rho_s, self_weight=self_weight,
                mode=mode, local_epochs=local_epochs,
            )

        fn, _ = self._get_program(cache_key, build)
        return fn
