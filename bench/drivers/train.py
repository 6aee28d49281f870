"""Training traffic: back-to-back federated training jobs through
``Engine.run``.

Set-up makes the telemetry on the device from the seed, builds one
``Engine`` and runs one job through it, which compiles (or loads from the
persistent cache) the cell's program.  The window then runs jobs back to
back on that same engine, each with fresh trial seeds, and closes at the
end of the job that is running when ``--seconds`` expire.  Each job trains
``seeds_per_job`` trials of the configuration's deployment for its
``rounds`` and evaluates the detector.

``correct`` compares one job of the window, drawn from the seed, with the
plain reference (``bench/reference/hfl.py``) run on the same trial keys
and the same telemetry at ``highest`` matmul precision: each round's
training loss, the change of every parameter leaf over the job, the F1 of
the detector, and the energy and participation of the physics.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, data
from bench.reference import hfl as ref

METHOD = "hfl-selective"


class _Store:
    """Where ``Engine.run`` hands the trained model of trial (seed 0,
    deployment 0), as it would to the serving path; kept in memory."""

    params = None

    def publish(self, step, params):
        self.params = params


class Driver:
    def __init__(self, cell: dict, seed: int, span):
        self.cell, self.cfg, self.traffic = cell, cell["config"], cell["traffic"]
        self.span = span
        self.rng = np.random.default_rng([seed, 1])
        self.data_key = int(np.random.default_rng([seed, 0]).integers(2**31 - 1))
        self.jobs: list = []

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        from repro.core.compression import CompressorConfig
        from repro.data.synthetic import SensorDataset
        from repro.engine import Engine
        from repro.launch import experiment as exp

        cfg, s = self.cfg, self.traffic["seeds_per_job"]
        with self.span("bench.data"):
            stacked = _stack(data.dataset(jax.random.key(self.data_key), cfg), s)
            n = cfg["n_sensors"]
            self.stacked = SensorDataset(
                *stacked, n_samples=jnp.full((s, n), float(cfg["train_len"])))
            jax.block_until_ready(self.stacked)
        self.hcfg = exp.make_config(
            n_sensors=cfg["n_sensors"], n_fog=cfg["n_fog"], rounds=cfg["rounds"],
            local_epochs=cfg["local_epochs"], batch_size=cfg["batch_size"],
            lr=cfg["lr"],
            compressor=CompressorConfig(rho_s=cfg["rho_s"], quant_bits=cfg["quant_bits"]),
        )
        self.engine = Engine(client_chunk=cfg["client_chunk"], hidden=tuple(cfg["hidden"]),
                             percentile=cfg["percentile"])
        self.store = _Store()
        with self.span("bench.warm"):
            self._job()

    def _seeds(self) -> tuple[int, ...]:
        return tuple(int(x) for x in self.rng.integers(0, 2**31 - 1, self.traffic["seeds_per_job"]))

    def _job(self):
        seeds = self._seeds()
        res = self.engine.run(METHOD, self.hcfg, seeds, self.stacked,
                              n_deployments=self.traffic["deployments"], store=self.store)
        return seeds, res.metrics, self.store.params

    # -- window ---------------------------------------------------------

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        while True:
            with self.span("bench.job"):
                self.jobs.append(self._job())
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0

    def work(self) -> int:
        """Sensor-rounds trained in the window."""
        t = self.traffic
        return (len(self.jobs) * t["seeds_per_job"] * t["deployments"]
                * self.cfg["n_sensors"] * self.cfg["rounds"])

    def end_to_end(self) -> dict[str, float]:
        return {"train_sensor_rounds_per_s": self.work() / self.window_s}

    def counters(self) -> dict:
        return {"jobs": len(self.jobs), "sensor_rounds": self.work(),
                "window_s": self.window_s}

    def hlo_texts(self) -> list[str]:
        """The compiled program the window ran, as text."""
        return [c.as_text() for c in self.engine.compiled()]

    def attempted_failed(self) -> tuple[int, int]:
        bad = sum(not bool(np.all(np.isfinite(np.asarray(m["losses"]))))
                  for _, m, _ in self.jobs)
        return len(self.jobs), bad

    # -- correct --------------------------------------------------------

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.engine = None

    def dataset(self) -> dict:
        return {k: getattr(self.stacked, k)[0] for k in ("train", "val", "test", "test_label")}

    def check_numbers(self) -> dict[str, float]:
        self.checked = self.jobs[int(self.rng.integers(len(self.jobs)))]
        seeds, metrics, params = self.checked
        self.want = reference(seeds, self.dataset(), self.cfg)
        got = {k: np.asarray(v)[:, 0] for k, v in metrics.items() if k != "params"}
        got["final"] = [[layer["w"], layer["b"]] for layer in params]
        return job_numbers(got, self.want, trial=0)


def job_numbers(got: dict, want: dict, trial: int) -> dict[str, float]:
    """One job against the reference: every round's loss of every trial,
    the change of each parameter leaf of trial ``trial`` over the job
    (``got["final"]`` holds only that trial's layers), the detector's F1
    and the physics of every trial."""
    leaves = lambda layers: [np.asarray(a) for layer in layers for a in layer]  # noqa: E731
    pick = lambda layers: [[np.asarray(a)[trial] for a in layer] for layer in layers]  # noqa: E731
    return {
        "loss_rel": check.max_rel(got["losses"], want["losses"]),
        "change_gap": check.change_gap(leaves(got["final"]), leaves(pick(want["final"])),
                                       leaves(pick(want["init"]))),
        "f1_abs": check.max_abs(got["f1"], want["f1"]),
        "physics_rel": max(check.max_rel(got["e_total"], want["e_total"]),
                           check.max_rel(got["participation"], want["participation"])),
    }


@functools.partial(jax.jit, static_argnums=(1,))
def _stack(ds: dict, s: int):
    return tuple(jnp.broadcast_to(ds[k], (s, *ds[k].shape))
                 for k in ("train", "val", "test", "test_label"))


def reference(seeds, ds: dict, cfg: dict, dtype=jnp.float32) -> dict:
    """The plain reference over one job's trials, keys as ``Engine.run``
    makes them for deployment 0 (``jax.random.key(seed)``)."""
    keys = jnp.stack([jax.random.key(s) for s in seeds])
    fn = jax.jit(functools.partial(ref.job, cfg=cfg, dtype=dtype))
    with jax.default_matmul_precision("highest"):
        return jax.device_get(fn(keys, ds))
