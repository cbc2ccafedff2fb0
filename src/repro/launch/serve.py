"""Serving driver: batched greedy generation with KV cache.

CPU-scale example:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --reduced \
      --batch 4 --prompt-len 32 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.configs import get_config
    from repro.models.transformer import get_model
    from repro.runtime.engine import InferenceEngine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    max_len = args.prompt_len + args.new_tokens + cfg.num_prefix_tokens + 8
    engine = InferenceEngine(cfg, params, max_len=max_len)

    rng = np.random.default_rng(args.seed)
    if cfg.family == "encoder":
        feats = jnp.asarray(rng.standard_normal(
            (args.batch, args.prompt_len, cfg.d_model)), jnp.dtype(cfg.dtype))
        t0 = time.time()
        logits = engine.encode(feats)
        print(f"encoded {feats.shape} -> {logits.shape} "
              f"in {time.time()-t0:.2f}s")
        return

    prompts = jnp.asarray(rng.integers(2, cfg.vocab_size,
                                       (args.batch, args.prompt_len)),
                          jnp.int32)
    kw = {}
    if cfg.family == "vlm":
        kw["prefix_emb"] = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.num_prefix_tokens, cfg.d_model)),
            jnp.dtype(cfg.dtype)) * 0.02
    t0 = time.time()
    out = engine.generate(prompts, max_new_tokens=args.new_tokens, **kw)
    dt = time.time() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s)")
    print("first sequence:", np.asarray(out[0]).tolist())


if __name__ == "__main__":
    main()
