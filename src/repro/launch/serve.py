"""Batched serving driver (decode loop with KV cache).

Serves a reduced-config model on CPU: prefill a batch of prompts, then
autoregressively decode with the per-family cache (KV / SSM state / RG-LRU
state).  The full-size decode shapes (decode_32k, long_500k) are exercised
via launch/dryrun.py on the 512-device mesh.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b \\
      --batch 4 --prompt-len 32 --new-tokens 16
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch import compile_cache
from repro.models import api


def prefill_into_cache(cfg, params, cache, prompts: jax.Array):
    """Feed prompt tokens one step at a time (teacher-forced prefill).

    Production prefill is the fused full-sequence step (prefill_32k path);
    the token-stepped variant here keeps the serving loop family-agnostic
    on CPU since every family exposes decode_step.
    """
    step = api.make_serve_step(cfg)

    def body(carry, tok):
        cache, _ = carry
        cache, logits = step(params, cache, tok[:, None])
        return (cache, logits), None

    (cache, logits), _ = jax.lax.scan(
        body,
        (cache, jnp.zeros((prompts.shape[0], 1, cfg.vocab_size), jnp.float32)),
        prompts.T,
    )
    return cache, logits


def decode_tokens(cfg, params, cache, last_logits, n_new: int, key):
    """Greedy/temperature sampling decode loop, one token per step."""
    step = api.make_serve_step(cfg)

    def body(carry, k):
        cache, logits = carry
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        cache, logits = step(params, cache, tok[:, None])
        return (cache, logits), tok

    (_, _), toks = jax.lax.scan(
        body, (cache, last_logits), jax.random.split(key, n_new)
    )
    return toks.T  # (batch, n_new)


def main(argv: list[str] | None = None) -> None:
    """Run the serving driver; ``argv`` defaults to ``sys.argv[1:]`` so
    callers (e.g. examples/serve_model.py) can pass args directly instead
    of mutating ``sys.argv``."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = configs.get(args.arch, reduced=True)
    key = jax.random.key(args.seed)
    k_p, k_prompt, k_dec = jax.random.split(key, 3)

    params = api.init_params(k_p, cfg)
    max_seq = args.prompt_len + args.new_tokens + 1
    cache = api.init_cache(cfg, args.batch, max_seq)
    prompts = jax.random.randint(
        k_prompt, (args.batch, args.prompt_len), 0, cfg.vocab_size
    )

    t0 = time.time()
    cache, logits = prefill_into_cache(cfg, params, cache, prompts)
    t_prefill = time.time() - t0

    t0 = time.time()
    toks = decode_tokens(cfg, params, cache, logits, args.new_tokens, k_dec)
    toks.block_until_ready()
    t_decode = time.time() - t0

    out = {
        "arch": args.arch,
        "batch": args.batch,
        "prompt_len": args.prompt_len,
        "new_tokens": args.new_tokens,
        "prefill_s": round(t_prefill, 2),
        "decode_s": round(t_decode, 2),
        "tok_per_s": round(args.batch * args.new_tokens / max(t_decode, 1e-9), 1),
        "sample_output": toks[0, :8].tolist(),
    }
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
