"""Training traffic for the Anomaly Transformer: back-to-back federated
training jobs through ``Engine.run`` with the window detector.

The jobs, the window and what they count are those of
``bench/drivers/train.py``; the engine trains the Anomaly Transformer at
the configuration's widths (``Engine(detector=...)``) in chunks of
``client_chunk`` sensors.

``correct`` compares one job of the window, drawn from the seed, with
the plain reference (``bench/reference/anomaly_transformer.py``) run on
the same trial key and the same telemetry at ``highest`` matmul
precision: each round's training loss (``loss_rel``) and mean
association discrepancy (``assdis_rel``), the change of every parameter
leaf over the job (``change_gap``), the F1 of the detector (``f1_abs``),
and the energy and participation of the physics (``physics_rel``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, data
from bench.drivers import train
from bench.reference import anomaly_transformer as ref
from repro.models import anomaly_transformer as at


def at_config(cfg: dict) -> at.ATConfig:
    return at.ATConfig(win_size=cfg["win_size"], d_model=cfg["d_model"],
                       n_heads=cfg["n_heads"], e_layers=cfg["e_layers"],
                       d_ff=cfg["d_ff"], k=cfg["k"])


class Driver(train.Driver):
    def setup(self) -> None:
        from repro.core.compression import CompressorConfig
        from repro.core.energy import EnergyParams
        from repro.engine import Engine
        from repro.launch import experiment as exp

        cfg = self.cfg
        with self.span("bench.data"):
            self.make_data()
        self.hcfg = exp.make_config(
            n_sensors=cfg["n_sensors"], n_fog=cfg["n_fog"], rounds=cfg["rounds"],
            local_epochs=cfg["local_epochs"], batch_size=cfg["batch_size"],
            lr=cfg["lr"],
            compressor=CompressorConfig(rho_s=cfg["rho_s"], quant_bits=cfg["quant_bits"]),
            energy=EnergyParams(e_init_j=cfg["e_init_j"]),
        )
        self.engine = Engine(client_chunk=cfg["client_chunk"],
                             detector=at.detector(at_config(cfg)),
                             percentile=cfg["percentile"])
        self.store = train._Store()
        with self.span("bench.warm"):
            self._job()

    def make_data(self) -> None:
        """The seeded telemetry, on the device, in the program's container."""
        from repro.data.synthetic import SensorDataset

        cfg, s = self.cfg, self.traffic["seeds_per_job"]
        stacked = train._stack(data.dataset(jax.random.key(self.data_key), cfg), s)
        self.stacked = SensorDataset(
            *stacked, n_samples=jnp.full((s, cfg["n_sensors"]), float(cfg["train_len"])))
        jax.block_until_ready(self.stacked)

    def check_numbers(self) -> dict[str, float]:
        self.checked = self.jobs[int(self.rng.integers(len(self.jobs)))]
        seeds, metrics, params = self.checked
        self.want = reference(seeds[0], self.dataset(), self.cfg)
        got = {k: np.asarray(v)[0, 0] for k, v in metrics.items() if k != "params"}
        got["final"] = params
        return job_numbers(got, self.want)


def job_numbers(got: dict, want: dict) -> dict[str, float]:
    """One trial against the reference: every round's loss and mean
    association discrepancy, the change of each parameter leaf over the
    job, the detector's F1 and the physics."""
    leaves = lambda tree: [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]  # noqa: E731
    return {
        "loss_rel": check.max_rel(got["losses"], want["losses"]),
        "assdis_rel": check.max_rel(got["assdis"], want["assdis"]),
        "change_gap": check.change_gap(leaves(got["final"]), leaves(want["final"]),
                                       leaves(want["init"])),
        "f1_abs": check.max_abs(got["f1"], want["f1"]),
        "physics_rel": max(check.max_rel(got["e_total"], want["e_total"]),
                           check.max_rel(got["participation"], want["participation"])),
    }


def reference(seed: int, ds: dict, cfg: dict, dtype=jnp.float32) -> dict:
    """The plain reference of the trial ``Engine.run`` makes for ``seed``
    and deployment 0 (``jax.random.key(seed)``)."""
    fn = jax.jit(lambda key, d: ref.trial(key, d, cfg, dtype))
    with jax.default_matmul_precision("highest"):
        return jax.device_get(fn(jax.random.key(seed), ds))
