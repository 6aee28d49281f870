"""Tiny cells for the benchmark's CPU tests: the real cells' entries and
limits with configurations and traffic cut to a size a test run holds."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec  # noqa: E402

TINY = dict(n_sensors=8, n_fog=2, rounds=2, local_epochs=1, train_len=64, val_len=16,
            test_len=32)


def tiny_config(**kw) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", "paper-n200.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["serving"] = dict(cfg["serving"], buckets=[8, 32])
    cfg.update(kw)
    return cfg


def tiny_cell(name: str) -> dict:
    """The cell ``name`` at a tiny size: its limits and metrics stay."""
    cell = spec.cell(name)
    traffic = dict(cell["traffic"])
    if traffic["kind"] == "train":
        cfg = tiny_config() if cell["config"]["client_chunk"] is None else \
            tiny_config(n_sensors=12, n_fog=3, client_chunk=5)
        traffic.update(seeds_per_job=min(2, traffic["seeds_per_job"]))
    elif traffic["loop"] == "open":
        cfg = tiny_config()
        traffic.update(rate_hz=200.0, rows=[1, 4], warm_rows=[5, 30])
    else:
        cfg = tiny_config()
        traffic.update(outstanding=4, rows=[16, 16], warm_rows=[30])
    return dict(cell, config=cfg, traffic=traffic)


def run_tiny(name: str, seed: int = 2**31 + 11, seconds: float = 0.3) -> dict:
    """One whole run of the tiny cell on the CPU, past the harness's look
    for a chip, with the persistent compilation cache left as it was."""
    from unittest import mock

    import jax

    from bench import run

    with mock.patch("repro.launch.compile_cache.enable", lambda: None):
        return run.run(tiny_cell(name), seed, seconds, False, jax.devices()[:1],
                       log=lambda msg: None)
