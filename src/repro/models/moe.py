"""Mixture-of-Experts block: top-k routing with capacity-based sorted dispatch.

Dispatch is the sort-scatter formulation (GShard/MaxText style): token→expert
assignments are sorted by expert id, laid out into a dense [E, capacity, h]
buffer (tokens over capacity are dropped), run through a stacked-expert GLU,
and combined back with the renormalized router probabilities.  The expert
dimension E is the sharding axis for expert parallelism — under GSPMD the
scatter/gather pair around the expert einsum lowers to the all-to-all pattern
the paper's §VII names as future work (see core/commodel.py MoE extension).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models import layers
from repro.models.layers import dense_init, mlp_apply, rms_norm

def init_moe_blocks(rng, cfg: ModelConfig, L: int, dtype):
    moe = cfg.moe
    ka, kr, k1, k2, k3, ks = jax.random.split(rng, 6)
    h, f, E = cfg.d_model, moe.expert_d_ff, moe.num_experts
    p = layers.init_attention(ka, cfg, L, dtype=dtype)
    p["router"] = dense_init(kr, (L, h, E), jnp.float32)
    p["we1"] = dense_init(k1, (L, E, h, f), dtype)
    p["we2"] = dense_init(k2, (L, E, f, h), dtype)
    p["we3"] = dense_init(k3, (L, E, h, f), dtype)
    if moe.num_shared_experts:
        sf = moe.shared_d_ff * moe.num_shared_experts
        p.update({f"s{k}": v for k, v in layers.init_mlp(
            ks, h, sf, cfg.activation, L, dtype).items()})
    p["ln1"] = jnp.zeros((L, cfg.d_model), dtype)
    p["ln2"] = jnp.zeros((L, cfg.d_model), dtype)
    return p


def moe_capacity(tokens: int, cfg: ModelConfig) -> int:
    moe = cfg.moe
    cap = int(math.ceil(tokens * moe.top_k / moe.num_experts
                        * moe.capacity_factor))
    return min(max(cap, moe.top_k), tokens * moe.top_k)


def moe_ffn(cfg: ModelConfig, p, x):
    """x: [B, S, h] -> (y [B, S, h], aux_loss scalar).  GSPMD path: the
    dispatch runs over the GLOBAL token set and the partitioner places the
    collectives (baseline for §Perf's local-dispatch comparison)."""
    moe = cfg.moe
    B, S, h = x.shape
    T = B * S
    cap = moe_capacity(T, cfg)
    xf = x.reshape(T, h)
    y, aux = _moe_compute(cfg, p, xf, cap)
    if moe.num_shared_experts:
        y = y + mlp_apply({"w1": p["sw1"], "w2": p["sw2"],
                           "w3": p.get("sw3")}, xf, cfg.activation)
    return y.reshape(B, S, h), aux


def _moe_compute(cfg: ModelConfig, p, xf, cap: int):
    """Core routed-expert computation on a flat token block [T, h].

    Shared by the GSPMD path (global tokens) and the shard_map local-dispatch
    path (per-data-shard tokens, f-sharded experts)."""
    moe = cfg.moe
    E, K = moe.num_experts, moe.top_k
    T, h = xf.shape

    router_logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(router_logits, axis=-1)                    # [T, E]
    top_p, top_i = jax.lax.top_k(probs, K)                            # [T, K]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    mean_prob = probs.mean(axis=0)
    frac_tok = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(mean_prob * frac_tok) * moe.router_aux_coef

    flat_e = top_i.reshape(-1)                                        # [T*K]
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_p = top_p.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    e_s, t_s, p_s = flat_e[order], flat_t[order], flat_p[order]
    counts = jnp.bincount(flat_e, length=E)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(T * K) - offsets[e_s]
    keep = pos_in_e < cap
    slot = jnp.where(keep, e_s * cap + pos_in_e, E * cap)             # overflow

    xe = jnp.zeros((E * cap + 1, h), xf.dtype).at[slot].set(xf[t_s])
    xe = xe[:E * cap].reshape(E, cap, h)
    up = jnp.einsum("ech,ehf->ecf", xe, p["we1"])
    gate = jax.nn.silu(up) if cfg.activation == "swiglu" else jax.nn.gelu(up)
    act = gate * jnp.einsum("ech,ehf->ecf", xe, p["we3"])
    ye = jnp.einsum("ecf,efh->ech", act, p["we2"]).reshape(E * cap, h)
    ye = jnp.concatenate([ye, jnp.zeros((1, h), ye.dtype)], axis=0)

    y = jnp.zeros((T, h), xf.dtype).at[t_s].add(
        ye[slot] * (p_s * keep).astype(ye.dtype)[:, None])
    return y, aux


def moe_ffn_local(cfg: ModelConfig, p, x, mesh):
    """§Perf local-dispatch MoE (shard_map): tokens never leave their data
    shard — routing/sort/scatter are shard-local, experts are tensor-parallel
    on the model axis (f-dim), and the ONLY cross-chip communication is one
    psum per MoE layer (the row-parallel expert down-projection).

    This replaces the GSPMD-partitioned global sort-scatter, whose data-
    dependent gather/scatter forces full-activation all-gathers across the
    mesh (the dominant collective term in the mixtral/deepseek baselines).
    """
    from jax.sharding import PartitionSpec as P

    B, S, h = x.shape
    moe = cfg.moe
    baxes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = 1
    for a in baxes:
        dp *= mesh.shape[a]
    bdim = baxes if (B % dp == 0 and B >= dp) else None
    t_loc = (B // dp if bdim else B) * S
    cap = moe_capacity(t_loc, cfg)

    x_spec = P(bdim, None, None)
    fsdp = cfg.moe_fsdp and "data" in mesh.shape and h % mesh.shape["data"] == 0
    d_ax = "data" if fsdp else None
    pspecs = {"router": P(None, None),
              "we1": P(None, d_ax, "model"), "we3": P(None, d_ax, "model"),
              "we2": P(None, "model", d_ax)}
    if moe.num_shared_experts:
        pspecs.update({"sw1": P(None, "model"), "sw3": P(None, "model"),
                       "sw2": P("model", None)})
    p_local = {k: p[k] for k in pspecs}

    def fn(p_l, x_l):
        Bl, Sl, _ = x_l.shape
        xf = x_l.reshape(Bl * Sl, h)
        if fsdp:   # just-in-time weight gather (ZeRO-3 for serving)
            p_l = dict(p_l,
                       we1=jax.lax.all_gather(p_l["we1"], "data", axis=1,
                                              tiled=True),
                       we3=jax.lax.all_gather(p_l["we3"], "data", axis=1,
                                              tiled=True),
                       we2=jax.lax.all_gather(p_l["we2"], "data", axis=2,
                                              tiled=True))
        y, aux = _moe_compute(cfg, p_l, xf, cap)
        if moe.num_shared_experts:
            y = y + mlp_apply({"w1": p_l["sw1"], "w2": p_l["sw2"],
                               "w3": p_l.get("sw3")}, xf, cfg.activation)
        y = jax.lax.psum(y, "model")          # row-parallel expert down-proj
        if bdim:
            aux = jax.lax.pmean(aux, bdim)
        return y.reshape(Bl, Sl, h), aux

    y, aux = jax.shard_map(fn, mesh=mesh, in_specs=(pspecs, x_spec),
                           out_specs=(x_spec, P()),
                           check_vma=False)(p_local, x)
    return y, aux


def moe_block_apply(cfg: ModelConfig, p, x, positions, mask,
                    cache=None, pos=None, build_cache_w=None):
    from repro.models.blocks import attention_apply
    from repro.runtime import meshctx
    attn_out, cache_out = attention_apply(
        cfg, p, rms_norm(x, p["ln1"], cfg.norm_eps), positions, mask,
        cache=cache, pos=pos, build_cache_w=build_cache_w)
    x = x + attn_out @ p["wo"]
    xn = rms_norm(x, p["ln2"], cfg.norm_eps)
    mesh = meshctx.get_mesh()
    if (cfg.moe_dispatch == "local" and mesh is not None
            and "model" in mesh.shape):
        y, aux = moe_ffn_local(cfg, p, xn, mesh)
    else:
        y, aux = moe_ffn(cfg, p, xn)
    return x + y, cache_out, aux
