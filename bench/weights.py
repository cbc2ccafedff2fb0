"""Random weights from the run's seed, made on the device in one jitted call.

The weights are the benchmark's, not the program's: the same numbers go
to the program under test and to the reference.  They take the layout
that the configuration's architecture module (``bench/arch/<arch>.py``)
gives in ``leaf_specs``, in the configuration's dtype unless a leaf names
its own, on the default device; the vocabulary is padded to a multiple
of 128 with zero rows.
"""
from __future__ import annotations

import numpy as np

from bench import modules


def padded_vocab(cfg: dict) -> int:
    return -(-int(cfg["vocab_size"]) // 128) * 128


def dtype_bytes(cfg: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["dtype"]]


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
    2**64), in one jitted call.  Leaf ``i`` in sorted path order draws
    from ``fold_in(key, i)``: normal times the leaf's std, in the
    configuration's dtype or the one its spec names third."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg["dtype"])
    specs = modules.arch(cfg).leaf_specs(cfg)
    V, pv = int(cfg["vocab_size"]), padded_vocab(cfg)

    def init(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        flat = {}
        for i, (path, (shape, std, *own)) in enumerate(sorted(specs.items())):
            k = jax.random.fold_in(key, i)
            a = jax.random.normal(k, shape, jnp.float32) * std
            if path == ("embed",) and pv != V:
                a = a.at[V:].set(0.0)
            if path == ("lm_head",) and pv != V:
                a = a.at[:, V:].set(0.0)
            flat[path] = a.astype(jnp.dtype(own[0]) if own else dtype)
        return _nest(flat)

    fn = jax.jit(init)
    seed = int(seed)
    return fn(np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF))
