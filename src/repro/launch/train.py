"""Training launcher.

Two entry modes:

  federated  — the paper's pipeline: hierarchical (or flat) federated
               anomaly-detector training over the simulated underwater
               acoustic network, with checkpointing and metric logs.

      PYTHONPATH=src python -m repro.launch.train federated \\
          --method hfl-selective --sensors 100 --fog 10 --rounds 20

  production — data-parallel training of an assigned architecture on the
               local mesh (reduced config on CPU; the full config is
               exercised via launch/dryrun.py on the 512-device mesh).

      PYTHONPATH=src python -m repro.launch.train production \\
          --arch llama3-8b --steps 20 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.checkpoint import CheckpointStore
from repro.data.synthetic import SyntheticConfig, generate, normalize
from repro.engine import Engine
from repro.launch import compile_cache
from repro.launch import experiment as exp
from repro.launch.mesh import make_mesh
from repro.models import api


def run_federated(args: argparse.Namespace) -> dict:
    """Train one cell through :class:`repro.engine.Engine`, so the kernel
    backend is the engine's: compiled Pallas on a TPU, the jnp oracles
    elsewhere (``compressor`` / ``local_solver`` in the output say which)."""
    cfg = exp.make_config(
        n_sensors=args.sensors,
        n_fog=args.fog,
        rounds=args.rounds,
        local_epochs=args.local_epochs,
        lr=args.lr,
    )
    ds = normalize(
        generate(
            jax.random.key(args.seed),
            SyntheticConfig(
                n_sensors=args.sensors, dirichlet_alpha=args.dirichlet_alpha
            ),
        )
    )
    eng = Engine()
    t0 = time.time()
    res = eng.run(args.method, cfg, (args.seed,), ds)
    wall = time.time() - t0
    m = {k: v[0, 0] for k, v in res.metrics.items()}
    solver = res.cfg.local_solver
    return {
        "mode": "federated",
        "method": args.method,
        "f1": float(m["f1"]),
        "participation": float(m["participation"]),
        "energy_j": {
            k: float(m[f"e_{k}"]) for k in ("total", "s2f", "f2f", "f2g")
        },
        "final_loss": float(m["losses"][-1]) if m["losses"].size else None,
        "compressor": eng.take_log()[-1]["compressor"],
        "local_solver": (
            ("pallas" if not solver.interpret else "pallas-interpret")
            if solver.use_pallas else "ref"
        ),
        "device": jax.devices()[0].device_kind,
        "wall_s": round(wall, 1),
    }


def run_production(args: argparse.Namespace) -> dict:
    cfg = configs.get(args.arch, reduced=not args.full)
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    key = jax.random.key(args.seed)
    params = api.init_params(key, cfg)
    step = api.make_train_step(cfg)

    store = CheckpointStore(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if store is not None and store.latest_step() is not None:
        params, start = store.restore(params)
        print(f"restored checkpoint at step {start}")

    batch_sh = NamedSharding(mesh, P("data"))
    jstep = jax.jit(step, in_shardings=(None, {"tokens": batch_sh}),
                    donate_argnums=(0,))

    losses = []
    t0 = time.time()
    with mesh:
        for i in range(start, start + args.steps):
            key, kb = jax.random.split(key)
            batch = {
                "tokens": jax.random.randint(
                    kb, (args.batch, args.seq), 0, cfg.vocab_size
                )
            }
            if cfg.family == "encdec":
                batch["audio_embeds"] = jax.random.normal(
                    kb, (args.batch, cfg.n_audio_frames, cfg.d_model), cfg.dtype
                )
            if cfg.n_visual_tokens > 0:
                batch["visual_embeds"] = jax.random.normal(
                    kb, (args.batch, cfg.n_visual_tokens, cfg.d_model), cfg.dtype
                )
                jstep_v = jax.jit(step, donate_argnums=(0,))
                params, loss = jstep_v(params, batch)
            else:
                params, loss = jstep(params, batch)
            losses.append(float(loss))
            if store is not None and (i + 1) % args.ckpt_every == 0:
                store.save(i + 1, params)
    wall = time.time() - t0
    if store is not None:
        store.save(start + args.steps, params)
    return {
        "mode": "production",
        "arch": args.arch,
        "steps": args.steps,
        "loss_first": losses[0],
        "loss_last": losses[-1],
        "wall_s": round(wall, 1),
        "finite": all(jnp.isfinite(jnp.asarray(losses)).tolist()),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="mode", required=True)

    fed = sub.add_parser("federated")
    fed.add_argument("--method", default="hfl-selective", choices=exp.METHODS)
    fed.add_argument("--sensors", type=int, default=100)
    fed.add_argument("--fog", type=int, default=10)
    fed.add_argument("--rounds", type=int, default=20)
    fed.add_argument("--local-epochs", type=int, default=5)
    fed.add_argument("--lr", type=float, default=0.01)
    fed.add_argument("--dirichlet-alpha", type=float, default=1.0)
    fed.add_argument("--seed", type=int, default=0)

    prod = sub.add_parser("production")
    prod.add_argument("--arch", required=True)
    prod.add_argument("--steps", type=int, default=10)
    prod.add_argument("--batch", type=int, default=4)
    prod.add_argument("--seq", type=int, default=64)
    prod.add_argument("--full", action="store_true",
                      help="full config (dry-run scale; not for CPU)")
    prod.add_argument("--ckpt-dir", default=None)
    prod.add_argument("--ckpt-every", type=int, default=100)
    prod.add_argument("--seed", type=int, default=0)

    args = ap.parse_args()
    compile_cache.enable()
    out = run_federated(args) if args.mode == "federated" else run_production(args)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
