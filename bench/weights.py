"""Random weights from the run's seed, made on the device in one jitted call.

The weights are the benchmark's, not the program's: the same numbers go
to the program under test and to the reference.  They take the layout the
program's dense decoder reads (stacked layers on a leading axis, norms
stored as offsets from 1, vocabulary padded to a multiple of 128 with
zero rows) in the configuration's dtype, on the default device.
"""
from __future__ import annotations

import numpy as np


def padded_vocab(cfg: dict) -> int:
    return -(-int(cfg["vocab_size"]) // 128) * 128


def leaf_specs(cfg: dict) -> dict:
    """{path: (shape, std)} of every leaf; std 0 means zeros."""
    h, L = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    D = int(cfg.get("head_dim") or h // H)
    ff, pv = int(cfg["intermediate_size"]), padded_vocab(cfg)
    s = lambda fan_in: 1.0 / np.sqrt(fan_in)
    specs = {
        ("blocks", "wq"): ((L, h, H * D), s(h)),
        ("blocks", "wk"): ((L, h, Hkv * D), s(h)),
        ("blocks", "wv"): ((L, h, Hkv * D), s(h)),
        ("blocks", "wo"): ((L, H * D, h), s(H * D)),
        ("blocks", "w1"): ((L, h, ff), s(h)),
        ("blocks", "w3"): ((L, h, ff), s(h)),
        ("blocks", "w2"): ((L, ff, h), s(ff)),
        ("blocks", "ln1"): ((L, h), 0.1),
        ("blocks", "ln2"): ((L, h), 0.1),
        ("final_norm",): ((h,), 0.1),
        ("embed",): ((pv, h), 1.0),
    }
    if not cfg.get("tie_word_embeddings"):
        specs[("lm_head",)] = ((h, pv), s(h))
    return specs


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        d = out
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = v
    return out


def make_params(cfg: dict, seed: int):
    """The parameter pytree for ``seed`` (any non-negative integer below
    2**64), in one jitted call."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg["dtype"])
    specs = leaf_specs(cfg)
    V, pv = int(cfg["vocab_size"]), padded_vocab(cfg)

    def init(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        flat = {}
        for i, (path, (shape, std)) in enumerate(sorted(specs.items())):
            k = jax.random.fold_in(key, i)
            a = jax.random.normal(k, shape, jnp.float32) * std
            if path == ("embed",) and pv != V:
                a = a.at[V:].set(0.0)
            if path == ("lm_head",) and pv != V:
                a = a.at[:, V:].set(0.0)
            flat[path] = a.astype(dtype)
        return _nest(flat)

    fn = jax.jit(init)
    seed = int(seed)
    return fn(np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF))

