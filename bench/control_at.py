"""Readings that set the limits of ``correct`` for ``train-at-smd-n200`` on
the chip.

    python3 bench/control_at.py [--seeds 12] [--controls 3] [--first 1000]

One process sets the cell up once.  For each seed it draws that seed's
telemetry and trial as a run would, trains one job through the engine and
reads it against the plain reference at ``highest`` precision, exactly as
``bench/run.py`` reads it (``program``).  On the first ``--controls``
seeds the same numbers are also read for the reference put in the
program's place:

* ``control``: computed in bfloat16, the precision below the
  configuration's float32;
* ``no_prior``: every prior association P replaced by uniform rows, so
  the association discrepancy no longer measures S against a Gaussian
  prior;
* ``lambda_sign``: the minimax signs swapped (k -> -k): S is pulled
  towards the prior and the prior pushed away;
* ``half``: the paper cells' physics fault (``bench/control.py``): the
  second half of the sensors never reach a fog.

A state left unchanged reads 1 by ``change_gap``'s measure and needs no
run.  A limit lies above the largest ``program`` reading and below the
smallest reading of a control or fault that separates from it.  One JSON
line per seed (also appended to ``chiprun_out/control_at.jsonl``), then
one summary line.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from unittest import mock  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
CELL = "train-at-smd-n200"


def _uniform_prior():
    import jax.numpy as jnp

    from bench.reference import anomaly_transformer as ref

    forward = ref.forward

    def no_prior(p, x, n_heads):
        x_hat, s, pr = forward(p, x, n_heads)
        return x_hat, s, jnp.full_like(pr, 1.0 / pr.shape[-1])

    return mock.patch.object(ref, "forward", no_prior)


def _half_reach():
    from bench import control

    return control._half_reach()


# name -> (config change, patch of the reference)
FAULTS = {
    "no_prior": (lambda cfg: {}, _uniform_prior),
    "lambda_sign": (lambda cfg: {"k": -cfg["k"]}, contextlib.nullcontext),
    "half": (lambda cfg: {}, _half_reach),
}


def controls(drv, seed: int) -> dict[str, dict[str, float]]:
    """Control and fault readings of the job ``drv.check_numbers`` read."""
    import jax.numpy as jnp

    from bench.drivers import train_at

    ds = drv.dataset()
    cases = {"control": (lambda cfg: {}, contextlib.nullcontext, jnp.bfloat16)}
    cases.update({k: (change, patch, jnp.float32) for k, (change, patch) in FAULTS.items()})
    out = {}
    for name, (change, patch, dtype) in cases.items():
        with patch():
            got = train_at.reference(seed, ds, dict(drv.cfg, **change(drv.cfg)), dtype=dtype)
        out[name] = train_at.job_numbers(got, drv.want)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first", type=int, default=1000)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import device, spec
    from bench.drivers import train_at
    from repro.launch import compile_cache

    cell = spec.cell(CELL)
    device.require(cell["chips"])
    compile_cache.enable()
    drv = train_at.Driver(cell, args.first, jax.profiler.TraceAnnotation)
    drv.setup()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    reads: dict[str, dict[str, float]] = {}
    for i, seed in enumerate(range(args.first, args.first + args.seeds)):
        drv.rng = np.random.default_rng([seed, 1])
        drv.data_key = int(np.random.default_rng([seed, 0]).integers(2**31 - 1))
        drv.make_data()
        drv.jobs = []
        drv.window(0.0)
        line = {"seed": seed, "program": drv.check_numbers()}
        if i < args.controls:
            line.update(controls(drv, drv.checked[0][0]))
        line["seconds"] = time.perf_counter() - T0
        print(json.dumps(line), flush=True)
        with open(os.path.join(out_dir, "control_at.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")
        for case, numbers in line.items():
            if case in ("seed", "seconds"):
                continue
            pick = max if case == "program" else min
            for k, v in numbers.items():
                reads.setdefault(case, {})[k] = pick(reads.get(case, {}).get(k, v), v)
    print(json.dumps({"workload": CELL, "seeds": args.seeds,
                      "program_max": reads.pop("program"), "min": reads,
                      "seconds": time.perf_counter() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
