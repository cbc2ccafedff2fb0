"""Transformer blocks: dense (GQA) block shared by dense/encoder/vlm families.

Block API (used by the scan trunk in ``transformer.py``):

    init_blocks(rng, cfg, L, dtype)              -> stacked param pytree [L, ...]
    block_apply(cfg, p_l, x, positions, mask, cache=None, pos=None,
                build_cache_w=None, block_table=None) -> (y, cache_out, aux)

``cache`` is the per-layer cache slice in decode mode; ``build_cache_w`` asks a
full-sequence pass to emit a (ring-buffer) cache of width W for the engine;
``block_table`` switches the dense block to the paged-cache path
(DESIGN.md §8), where ``cache`` is a [P, ps, Hkv, D] page pool.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models import layers
from repro.models.layers import (apply_rope, gqa_attention,
                                 mlp_apply, rms_norm)


def build_ring_cache(k, v, w: int):
    """Seed a ring-buffer cache of width W from full-sequence K/V [B,S,Hkv,D].

    Absolute position p lives in slot p % W; for S <= W this is the identity
    layout (right-padded), for S > W we scatter the last W positions.
    """
    B, S, Hkv, D = k.shape
    if S <= w:
        pad = [(0, 0), (0, w - S), (0, 0), (0, 0)]
        return {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    slots = jnp.arange(S - w, S) % w
    ck = jnp.zeros((B, w, Hkv, D), k.dtype).at[:, slots].set(k[:, S - w:])
    cv = jnp.zeros((B, w, Hkv, D), v.dtype).at[:, slots].set(v[:, S - w:])
    return {"k": ck, "v": cv}


def attention_apply(cfg: ModelConfig, p, xn, positions, mask,
                    cache=None, pos=None, build_cache_w=None, n_heads=None,
                    block_table=None, cp_axis=None, cp_size: int = 1):
    """Self-attention over a normalized input xn [B,S,h].

    Returns (attn_out [B,S,n_heads*D], cache_out).

    ``cp_axis`` switches a full-sequence pass to the context-parallel ring
    branch (DESIGN.md §9; must run inside shard_map with that mesh axis):
    xn is this worker's [B, S/c, h] sequence shard and ``positions`` its
    absolute positions; the local K/V blocks rotate around the cp ring
    (``layers.ring_kv_assemble``, 2·(c-1) collective-permutes) so queries
    attend over the full assembled sequence, and ``mask`` must already be
    the shard-offset causal mask ([S/c, S]).  A ``build_cache_w`` cache is
    seeded from the assembled K/V, i.e. it comes out whole on every cp
    worker — the gather-into-slots handoff needs no further collective.
    """
    n_heads = n_heads or cfg.num_heads
    B, S, _ = xn.shape
    D, Hkv = cfg.head_dim, cfg.num_kv_heads
    q = (xn @ p["wq"]).reshape(B, S, n_heads, D)
    k = (xn @ p["wk"]).reshape(B, S, Hkv, D)
    v = (xn @ p["wv"]).reshape(B, S, Hkv, D)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cp_axis is not None:
        if cache is not None:
            raise ValueError("context parallelism is prefill-only: decode "
                             "runs replicated over the cp axis")
        k = layers.ring_kv_assemble(k, cp_axis, cp_size)
        v = layers.ring_kv_assemble(v, cp_axis, cp_size)

    if cache is not None and block_table is not None:
        # paged path (DESIGN.md §8): the chunk's K/V rows are scattered into
        # the [P, ps, Hkv, D] page pool at the pages the block table names,
        # then the logical view is gathered back for attention.  Serves both
        # chunked prefill (S > 1) and paged decode (S == 1); ``pos`` is the
        # [B] vector of start positions.
        with jax.named_scope("page_write"):
            ck, cv = layers.paged_cache_update(cache["k"], cache["v"], k, v,
                                               pos, block_table)
        with jax.named_scope("page_gather"):
            kg = layers.paged_gather(ck, block_table)
            vg = layers.paged_gather(cv, block_table)
        pmask = layers.paged_attn_mask(kg.shape[1], pos, S)
        out = gqa_attention(q, kg, vg, pmask)
        cache_out = {"k": ck, "v": cv}
    elif cache is not None:
        # single-token decode against a ring-buffer cache; ``pos`` is a
        # scalar (fixed-batch serve path) or [B] per-sequence positions
        # (continuous batching: each sequence hits its own slot and mask)
        w = cache["k"].shape[1]
        ck, cv = layers.ring_cache_update(cache["k"], cache["v"], k, v, pos)
        dmask = layers.decode_attn_mask(w, pos, cfg.sliding_window)
        out = gqa_attention(q, ck, cv, dmask)
        cache_out = {"k": ck, "v": cv}
    else:
        if isinstance(mask, layers.MaskSpec):
            # flash-style chunked attention (cfg.attention_impl == "chunked")
            out = layers.chunked_gqa_attention(q, k, v, mask,
                                               kv_chunk=cfg.attention_chunk)
        else:
            out = gqa_attention(q, k, v, mask)
        cache_out = None
        if build_cache_w is not None:
            cache_out = build_ring_cache(k, v, build_cache_w)
    return out.reshape(B, S, n_heads * D), cache_out


def init_dense_blocks(rng, cfg: ModelConfig, L: int, dtype):
    ka, km, kn = jax.random.split(rng, 3)
    p = layers.init_attention(ka, cfg, L, dtype=dtype)
    p.update(layers.init_mlp(km, cfg.d_model, cfg.d_ff, cfg.activation, L, dtype))
    p["ln1"] = jnp.zeros((L, cfg.d_model), dtype)
    p["ln2"] = jnp.zeros((L, cfg.d_model), dtype)
    return p


def dense_block_apply(cfg: ModelConfig, p, x, positions, mask,
                      cache=None, pos=None, build_cache_w=None,
                      block_table=None, cp_axis=None, cp_size: int = 1):
    with jax.named_scope("attention"):
        attn_out, cache_out = attention_apply(
            cfg, p, rms_norm(x, p["ln1"], cfg.norm_eps), positions, mask,
            cache=cache, pos=pos, build_cache_w=build_cache_w,
            block_table=block_table, cp_axis=cp_axis, cp_size=cp_size)
        x = x + attn_out @ p["wo"]
    with jax.named_scope("mlp"):
        x = x + mlp_apply(p, rms_norm(x, p["ln2"], cfg.norm_eps),
                          cfg.activation)
    return x, cache_out, jnp.zeros((), jnp.float32)
