"""Pair C measurement: llama3-8b train_4k on a multipod mesh — standard
data-parallel train_step (baseline) vs the compressed selective cross-pod
HFL step (core/mesh_fl.py).

NOTE: XLA's SPMD partitioner CHECK-fails on mixed manual/auto shard_map
at the full 2x16x16 mesh (spmd_partitioner_util.cc:504, device-group
mismatch — a compiler limitation, not a model property), so this A/B runs
on a reduced 2x4x4 multipod mesh for BOTH arms; the comparison metric is
the relative cross-pod collective traffic.

  PYTHONPATH=src python experiments/perf/run_pair_c.py [baseline|hfl] [rho]
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=32 "
    + os.environ.get("XLA_FLAGS", "")
)
import json  # noqa: E402
import sys   # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs.base import SHAPES  # noqa: E402
from repro.core import mesh_fl  # noqa: E402
from repro.launch import dryrun, roofline, sharding as shlib  # noqa: E402
from repro.launch.mesh import make_mesh, make_production_mesh  # noqa: E402
from repro.models import api  # noqa: E402

ARCH, SHAPE = "llama3_8b", "train_4k"


def make_small_multipod():
    return make_mesh((2, 4, 4), ("pod", "data", "model"))


def lower_hfl(cfg, mesh, rho, comp_mode="int8"):
    params_abs = api.abstract_params(cfg)
    params_sh = shlib.tree_shardings(params_abs, api.param_axes(cfg), mesh)
    n_pods = mesh.shape["pod"]
    err_abs = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((n_pods, *l.shape), jnp.float32),
        params_abs,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P
    # err must mirror the gradient leaf shardings (pod + the param spec),
    # otherwise v = g + err forces dense f32 regathers of every leaf.
    err_sh = jax.tree_util.tree_map(
        lambda psh: NamedSharding(mesh, P("pod", *psh.spec)), params_sh
    )
    specs = api.input_specs(cfg, SHAPES[SHAPE])
    specs_sh = shlib.batch_shardings(specs, mesh)
    step = mesh_fl.make_pod_hfl_train_step(cfg, mesh, rho_s=rho, mode=comp_mode)
    with jax.sharding.set_mesh(mesh):
        lowered = jax.jit(
            step,
            in_shardings=(params_sh, err_sh, specs_sh),
            out_shardings=(params_sh, err_sh, None),
            donate_argnums=(0, 1),
        ).lower(params_abs, err_abs, specs)
        return lowered.compile()




def _lower_plain(cfg, mesh):
    params_abs = api.abstract_params(cfg)
    params_sh = shlib.tree_shardings(params_abs, api.param_axes(cfg), mesh)
    specs = api.input_specs(cfg, SHAPES[SHAPE])
    specs_sh = shlib.batch_shardings(specs, mesh)
    fn = api.make_train_step(cfg)
    with jax.sharding.set_mesh(mesh):
        lowered = jax.jit(
            fn,
            in_shardings=(params_sh, specs_sh),
            out_shardings=(params_sh, None),
            donate_argnums=(0,),
        ).lower(params_abs, specs)
        return lowered.compile()


def _to_rec(base, c1, c2):
    cost1, cost2 = c1.cost_analysis(), c2.cost_analysis()
    coll1 = dryrun.collective_bytes(c1.as_text())
    coll2 = dryrun.collective_bytes(c2.as_text())
    L = base.n_layers

    def extrap(a, b):
        return a + (L - 1) * max(b - a, 0.0)

    return {
        "arch": ARCH, "shape": SHAPE, "status": "ok", "kind": "train",
        "mesh": [2, 4, 4], "axes": ["pod", "data", "model"],
        "chips": 32,
        "flops": cost1.get("flops"),
        "bytes_accessed": cost1.get("bytes accessed"),
        "collectives": coll1,
        "corrected": {
            "flops": extrap(cost1["flops"], cost2["flops"]),
            "bytes_accessed": extrap(
                cost1["bytes accessed"], cost2["bytes accessed"]
            ),
            "collective_total": extrap(coll1["total"], coll2["total"]),
        },
        "coll_by_type_raw": {k: v for k, v in coll1.items() if k != "count"},
        "memory": {},
    }

def main() -> None:
    mode = sys.argv[1] if len(sys.argv) > 1 else "hfl"
    comp_mode = sys.argv[3] if len(sys.argv) > 3 else "int8"
    rho = float(sys.argv[2]) if len(sys.argv) > 2 else 0.05
    mesh = make_small_multipod()

    if mode == "baseline":
        base = configs.get(ARCH)
        c1 = _lower_plain(base.replace(scan_unroll=1), mesh)
        c2 = _lower_plain(base.replace(scan_unroll=2), mesh)
        rec = _to_rec(base, c1, c2)
    else:
        base = configs.get(ARCH)
        c1 = lower_hfl(base.replace(scan_unroll=1), mesh, rho, comp_mode)
        c2 = lower_hfl(base.replace(scan_unroll=2), mesh, rho, comp_mode)
        rec = _to_rec(base, c1, c2)

    row = roofline.analyse(rec)
    out = {"tag": f"pairC_{mode}_{comp_mode}_rho{rho}", **row}
    out["coll_by_type_raw"] = rec["coll_by_type_raw"]
    if mode != "baseline":
        d = sum(
            int(jnp.prod(jnp.asarray(l.shape)))
            for l in jax.tree_util.tree_leaves(
                api.abstract_params(configs.get(ARCH))
            )
        )
        out["wire_bytes_compact"] = mesh_fl.wire_bytes(d, rho)
        out["wire_bytes_dense_f32"] = 4.0 * d
    os.makedirs("experiments/perf", exist_ok=True)
    with open("experiments/perf/log.jsonl", "a") as f:
        f.write(json.dumps(out, default=str) + "\n")
    print(json.dumps(out, indent=1, default=str))


if __name__ == "__main__":
    main()
