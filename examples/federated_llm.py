"""The paper's technique at LLM scale on the TPU mesh (DESIGN.md §3).

Hierarchical federated fine-tuning of a (reduced) llama3 on the production
mesh layout: clients live on the `data` axis, pods play the fog-cluster
role, and the three paper components map onto mesh collectives:

  sensor->fog upload        -> in-pod weighted psum over `data`
  fog->gateway uplink       -> cross-pod psum over `pod`
  Top-K+EF+int8 compression -> per-client update compression BEFORE the
                               expensive cross-pod hop (kernels/)
  selective fog cooperation -> ring collective_permute mixing over `pod`

On CPU this runs with a 1x1 mesh (the collectives are identities) — the
same program lowers unchanged to the 2x16x16 production mesh, which is
exactly what launch/dryrun.py proves.

  PYTHONPATH=src python examples/federated_llm.py
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core import aggregation as agg
from repro.core import compression as comp
from repro.data.pipeline import lm_batches
from repro.launch.mesh import make_mesh, shard_map_compat
from repro.models import api


def main() -> None:
    cfg = configs.get("llama3-8b", reduced=True)
    mesh = make_mesh((1, 1), ("pod", "data"))
    key = jax.random.key(0)
    params = api.init_params(key, cfg)
    lfn = api.loss_fn(cfg)
    compressor = comp.CompressorConfig(rho_s=0.05, quant_bits=8,
                                       mode="blockwise")

    # Synthetic token stream per client shard.
    stream = jax.random.randint(jax.random.key(1), (4096,), 0, cfg.vocab_size)

    from jax.flatten_util import ravel_pytree
    flat0, unravel = ravel_pytree(params)
    err0 = jnp.zeros_like(flat0)

    def local_round(params, err, key):
        """One client's local step + compressed update (per data shard)."""
        batch = {"tokens": lm_batches(key, stream, 2, 32)}
        loss, grads = jax.value_and_grad(lfn)(params, batch)
        delta = jax.tree_util.tree_map(lambda g: -1e-3 * g, grads)
        recon, new_err = comp.compress_update(delta, err, compressor)
        return recon, new_err, loss

    def fed_step(params, err, key):
        recon, new_err, loss = local_round(params, err, key)
        # Hierarchical aggregation: cheap in-pod hop, expensive cross-pod
        # hop on the ALREADY-COMPRESSED update (beyond-paper optimisation).
        update = agg.hierarchical_mean(
            recon, jnp.float32(1.0), intra_axis="data", inter_axis="pod"
        )
        # Selective-cooperation analogue: light gossip over the pod ring.
        update = agg.ring_mix(update, 0.2, axis="pod")
        new_params = jax.tree_util.tree_map(
            lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
            params, update,
        )
        return new_params, new_err, jax.lax.pmean(loss, "data")

    sharded = jax.jit(
        shard_map_compat(
            fed_step,
            mesh=mesh,
            in_specs=(P(), P(), P()),
            out_specs=(P(), P(), P()),
        )
    )

    err = err0
    d = flat0.shape[0]
    bits = comp.payload_bits(d, compressor)
    print(f"model: reduced llama3 ({d:,} params)")
    print(f"compressed cross-pod payload: {bits / 8 / 1024:.1f} KiB "
          f"(vs {32 * d / 8 / 1024:.1f} KiB dense, "
          f"{comp.compression_ratio(d, compressor):.1%})")
    for step in range(5):
        key, k = jax.random.split(key)
        params, err, loss = sharded(params, err, k)
        print(f"step {step}: loss {float(loss):.4f}")
    print("same program lowers to the 2x16x16 mesh — see launch/dryrun.py")


if __name__ == "__main__":
    main()
