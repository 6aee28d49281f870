"""Readings that set the limits of ``correct``, for one cell on the chip.

    python3 bench/control.py --workload <cell> [--seeds 12] [--controls 3] [--first 1000]

For each seed, in this one process, the cell is set up as a run would set
it up and its window is driven (for training, one job; for serving, a
whole window at the cell's load), and what the timed path produced is
read against the plain reference at ``highest`` precision, exactly as
``bench/run.py`` reads it (``program``).  On the first ``--controls``
seeds the same numbers are also read for:

* ``control``: the reference itself put in the program's place and
  computed in bfloat16, the precision below the configuration's float32;
* training faults planted in the reference put in the program's place:
  ``half`` (the second half of the sensors never reach a fog, so each fog
  averages over the rest), ``energy`` (the uplink payload counted as the
  dense float32 update, an energy answer altered where it is produced)
  and ``threshold`` (the detector calibrated at the 98th percentile, an
  F1 answer altered where it is produced).  A state left unchanged reads
  1 by ``change_gap``'s measure and needs no run.

A limit lies above the largest ``program`` reading and below the
smallest reading of a control or fault that separates from it.  One JSON
line per seed, then one summary line.  The benchmark's own runs never
run this.
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


def _half_reach():
    from bench.reference import hfl as ref

    associate = ref.associate

    def half(sensor_pos, fog_pos):
        import jax.numpy as jnp

        fog, reach, dist = associate(sensor_pos, fog_pos)
        n = reach.shape[0]
        return fog, reach & (jnp.arange(n) < n // 2), dist

    return mock.patch.object(ref, "associate", half)


def _dense_payload():
    from bench.reference import hfl as ref

    return mock.patch.object(ref, "payload_bits", lambda d, k, bits: 32 * d)


# name -> (config change, patch of the reference)
TRAIN_FAULTS = {
    "half": ({}, _half_reach),
    "energy": ({}, _dense_payload),
    "threshold": ({"percentile": 98.0}, contextlib.nullcontext),
}


def control_train(drv, faults: bool = True) -> dict[str, dict[str, float]]:
    """Control and fault readings of the job ``drv.check_numbers`` read."""
    import jax.numpy as jnp

    from bench.drivers import train

    seeds = drv.checked[0]
    ds = drv.dataset()
    out = {}
    cases = {"control": ({}, contextlib.nullcontext, jnp.bfloat16)}
    if faults:
        cases.update({k: (cfg, patch, jnp.float32) for k, (cfg, patch) in TRAIN_FAULTS.items()})
    for name, (change, patch, dtype) in cases.items():
        with patch():
            got = train.reference(seeds, ds, dict(drv.cfg, **change), dtype=dtype)
        got["final"] = [[a[0] for a in layer] for layer in got["final"]]
        out[name] = train.job_numbers(got, drv.want, trial=0)
    return out


def control_serve(drv, faults: bool = True) -> dict[str, dict[str, float]]:
    import jax.numpy as jnp
    import numpy as np

    from bench.drivers import serve

    rows = jnp.asarray(np.concatenate([drv.test[s, a:a + n] for s, a, n in drv.reqs]))
    want = serve.reference_errors(drv.params, rows)
    got = serve.reference_errors(drv.params, rows, dtype=jnp.bfloat16)
    return {"control": serve.score_numbers(got, got > drv.tau, want, drv.tau, 0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first", type=int, default=1000)
    args = ap.parse_args(argv)

    import jax

    from bench import device, spec
    from repro.launch import compile_cache

    cell = spec.cell(args.workload)
    device.require(cell["chips"])
    compile_cache.enable()
    kind = cell["traffic"]["kind"]
    seconds = 0.0 if kind == "train" else spec.load_benchmark()["run_seconds"]
    mod = spec.driver(kind)
    reads: dict[str, dict[str, float]] = {}
    for i, seed in enumerate(range(args.first, args.first + args.seeds)):
        drv = mod.Driver(cell, seed, jax.profiler.TraceAnnotation)
        drv.setup()
        drv.window(seconds)
        drv.release()
        line = {"seed": seed, "program": drv.check_numbers()}
        if i < args.controls:
            line.update((control_train if kind == "train" else control_serve)(drv))
        print(json.dumps(line), flush=True)
        for case, numbers in line.items():
            if case == "seed":
                continue
            pick = max if case == "program" else min
            for k, v in numbers.items():
                reads.setdefault(case, {})[k] = pick(reads.get(case, {}).get(k, v), v)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "program_max": reads.pop("program"), "min": reads,
                      "seconds": time.perf_counter() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
