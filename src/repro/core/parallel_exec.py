"""Explicit TP / PP / hybrid inference engines (paper-faithful schedule).

The production path (runtime/, launch/) relies on GSPMD to place collectives.
This module instead reproduces the *exact* collective schedule the paper
profiles in vLLM/Megatron, using shard_map with hand-placed collectives:

  TP   (Section III-A): vocab-parallel embedding psum (+1), per layer one
       psum after the attention output projection and one after the MLP
       down-projection (2L), and a logits gather over the vocab shards.
  PP   (Section III-B): per stage boundary TWO tensors (vLLM ships
       hidden_states and residual separately — we split the activation into
       two summands to reproduce the wire pattern) moved by ``jax.device_put``
       between the per-stage jits and logged as TransferRecords, our measured
       Eq. 2 / Table V side (DESIGN.md §3 — not ppermute: an SPMD-lockstep
       collective would run every stage's schedule on every rank).
  TP×PP (Section III-C): per-stage allreduces (2L/p + 1), boundary p2p of
       the [tokens, h/t] shard, and 2 allgathers to redistribute the
       received shard among the stage's TP workers.

XLA adaptation (DESIGN.md §2): the paper's NCCL `Gather` of logit shards has
no XLA equivalent; we all-gather (commodel gather_mode="allgather").

These engines cover the dense llama-family (the paper's subjects).

Two execution modes (DESIGN.md §5):

  unroll=True   paper-parity mode.  Layer loops are unrolled so every
                collective appears as a distinct HLO op — the per-op count
                parity with Tables III–VI is asserted against the compiled
                module.
  unroll=False  fast path (default for benchmarks/ and runtime/).  Block
                params keep their stacked [L, ...] leading axis and the layer
                loop runs under ``jax.lax.scan`` inside one shard_map, so the
                module stays O(1) in depth; decode jits donate the KV cache
                so XLA updates the [L, B, W, kv, D] buffers in place; and
                ``tp_generate`` fuses N greedy decode steps into a single
                dispatch with ``lax.fori_loop``.  Collective *counts* are
                unchanged — core/hlo_comm.py expands scan trip counts, so
                both modes report identical schedules.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.config.base import ModelConfig
from repro.core.commodel import DEFAULT_QUANT_CHUNK, stage_layer_partition
from repro.kernels.quant_collective import (QUANT_DTYPES, chunk_amax,
                                            chunk_dequantize, chunk_quantize,
                                            collective_qmax, nibble_pack,
                                            nibble_unpack, scales_from_amax)
from repro.models.layers import apply_rope, decode_attn_mask, \
    decode_positions, gqa_attention, make_mask, mlp_apply, paged_attn_mask, \
    paged_layer_gather, paged_layer_write, ring_cache_update, \
    ring_kv_assemble, rms_norm
from repro.models.transformer import greedy_decode_host_loop, \
    greedy_decode_loop


# ---------------------------------------------------------------------------
# parameter partition specs (shared with Model.init pytrees)
# ---------------------------------------------------------------------------


def tp_param_specs(cfg: ModelConfig, tp_axis: str = "tp",
                   stage_axis: str = None) -> dict:
    """PartitionSpecs for a Model.init(...) pytree under explicit TP (+PP).

    Column-parallel: wq/wk/wv, w1/w3 (output dim sharded).  Row-parallel:
    wo, w2 (input dim sharded).  Vocab-parallel: embed, lm_head.
    With ``stage_axis``, block params gain a leading stage dimension.
    ``tp_axis=None`` yields fully replicated specs (a t=1 engine on a
    cp-only mesh).
    """
    st = (stage_axis,) if stage_axis else ()
    blk = {
        "wq": P(*st, None, None, tp_axis), "wk": P(*st, None, None, tp_axis),
        "wv": P(*st, None, None, tp_axis), "wo": P(*st, None, tp_axis, None),
        "w1": P(*st, None, None, tp_axis), "w3": P(*st, None, None, tp_axis),
        "w2": P(*st, None, tp_axis, None),
        "ln1": P(*st, None, None), "ln2": P(*st, None, None),
    }
    return {
        "blocks": blk,
        "embed": P(tp_axis, None),
        "lm_head": P(None, tp_axis),
        "final_norm": P(None),
    }


def place_params(params, specs, mesh: Mesh):
    """``device_put`` a parameter pytree onto ``mesh`` under ``specs`` —
    done once per engine, so no jitted step reshards the model per call."""
    return jax.device_put(params, jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: isinstance(x, P)))


def tp_place_params(cfg: ModelConfig, params, mesh: Mesh):
    """Place a ``Model.init`` pytree on a single-stage engine mesh with
    ``tp_param_specs`` (vocab/column/row shards on "tp", replicated over
    "cp")."""
    _, axis = _tp_axis_of(mesh)
    return place_params(params, tp_param_specs(cfg, tp_axis=axis), mesh)


# ---------------------------------------------------------------------------
# local (per-shard) building blocks
# ---------------------------------------------------------------------------


def _vocab_parallel_embed(embed_local, tokens, axis: str):
    """Vocab-sharded embedding lookup + psum (the paper's '+1' allreduce)."""
    idx = jax.lax.axis_index(axis)
    vshard = embed_local.shape[0]
    local = tokens - idx * vshard
    valid = (local >= 0) & (local < vshard)
    x = embed_local[jnp.clip(local, 0, vshard - 1)]
    x = jnp.where(valid[..., None], x, 0)
    return jax.lax.psum(x, axis)


def _maybe_psum(x, axis):
    """psum over the TP axis — identity when the layer runs full-width
    (``axis=None``, the pure-PP per-stage path)."""
    return jax.lax.psum(x, axis) if axis is not None else x


def _check_quant(quant):
    if quant is not None and quant not in QUANT_DTYPES:
        raise ValueError(f"unknown quant_collectives mode {quant!r}; "
                         f"expected None or one of {sorted(QUANT_DTYPES)}")
    return quant


def quantized_psum(x, axis, t: int, quant: str = "int8",
                   chunk: int = DEFAULT_QUANT_CHUNK):
    """Quantized two-step all-reduce over the TP axis (DESIGN.md §12).

    Lowers one full-width ``psum`` of x [..., h] into the Flash
    Communication decomposition:

      1. per-chunk abs-max + f32 ``pmax`` over the axis (the scale
         exchange — one small f32 all-reduce of [rows, ceil(h/chunk)]),
      2. symmetric quantize onto the shared scales, with ``floor(127/t)``
         (int8) / ``448/t`` (fp8-e4m3) headroom so the t-way sum cannot
         overflow the wire dtype — the int8 reduction is therefore EXACT,
      3. ``psum_scatter`` of the 1-byte payload (compiles to a genuine
         reduce-scatter HLO op over the quant dtype),
      4. ``all_gather`` of the reduced 1-byte shards,
      5. dequantize with the same shared scales (known on every rank from
         the pmax) back to x.dtype.

    ``quant="int4"`` swaps steps 2–4 for the packed-nibble variant: the
    reduce-scatter cannot carry 4-bit fields (integer partial sums would
    bleed across nibble boundaries), so the payload rides a tiled
    ``all_to_all`` instead — each rank receives every rank's packed copy
    of its own hidden block, unpacks, sums EXACTLY in int32 (|sum| <= 7t,
    which is why int4 keeps the full +-7 grid, see ``collective_qmax``),
    requantizes the reduced block by t back onto the 4-bit grid, and
    all-gathers the re-packed halves.  The wire moves 0.5 bytes/element on
    both hops; dequant runs at ``scales * t`` to undo the requantize.

    Identity fallbacks: ``axis=None`` / ``quant=None`` / ``t<=1`` run the
    plain ``_maybe_psum`` — bitwise-identical to the unquantized path with
    zero quant ops in the compiled module.
    """
    if axis is None or quant is None or t <= 1:
        return _maybe_psum(x, axis)
    h = x.shape[-1]
    if h % t:
        raise ValueError(f"quantized_psum scatters the hidden axis over "
                         f"t={t}: h={h} must divide")
    qmax = collective_qmax(quant, t)
    amax = jax.lax.pmax(chunk_amax(x, chunk), axis)
    scales = scales_from_amax(amax, qmax)
    q = chunk_quantize(x, scales, chunk, quant)
    if quant == "int4":
        if h % (2 * t):
            raise ValueError(f"int4 packs two values per byte and ships "
                             f"h/t-element blocks: h={h} must divide 2t="
                             f"{2 * t}")
        # half-split packing (byte i = elements i and i + h/2): packed block
        # j carries hidden slices j of BOTH halves, so rank j reduces those
        pa = jax.lax.all_to_all(nibble_pack(q), axis,
                                split_axis=x.ndim - 1,
                                concat_axis=x.ndim - 1, tiled=True)
        qa = nibble_unpack(pa)          # [low halves | high halves] of the
        #                                 t source copies of the local block
        r = qa.astype(jnp.int32).reshape(*x.shape[:-1], 2, t, h // (2 * t)) \
              .sum(axis=-2).reshape(*x.shape[:-1], h // t)  # exact: |r| <= 7t
        rq = jnp.clip(jnp.round(r.astype(jnp.float32) / t),
                      -7, 7).astype(jnp.int8)
        pg = jax.lax.all_gather(nibble_pack(rq), axis, axis=x.ndim - 1,
                                tiled=True)
        return chunk_dequantize(nibble_unpack(pg), scales * t, chunk,
                                x.dtype)
    qs = jax.lax.psum_scatter(q, axis, scatter_dimension=x.ndim - 1,
                              tiled=True)
    qg = jax.lax.all_gather(qs, axis, axis=x.ndim - 1, tiled=True)
    return chunk_dequantize(qg, scales, chunk, x.dtype)


def _tp_layer_qkv(cfg, pl, xn, positions, heads_t: int, kv_t: int):
    """Normed input [B, S, h] -> (RoPE'd q, RoPE'd k, v), each
    [B, S, H_t, D] — the projection head shared by every layer variant."""
    B, S = xn.shape[:2]
    D = cfg.head_dim
    q = apply_rope((xn @ pl["wq"]).reshape(B, S, heads_t, D), positions,
                   cfg.rope_theta)
    k = apply_rope((xn @ pl["wk"]).reshape(B, S, kv_t, D), positions,
                   cfg.rope_theta)
    v = (xn @ pl["wv"]).reshape(B, S, kv_t, D)
    return q, k, v


def _tp_layer_out(cfg, pl, x, attn, axis, t: int = 1, quant: str = None,
                  quant_chunk: int = DEFAULT_QUANT_CHUNK):
    """Attention-output + MLP residual tail shared by every layer variant:
    the layer's TWO psums when TP-sharded (``axis`` set).  With ``quant``
    each psum lowers to the quantized two-step (``quantized_psum``,
    DESIGN.md §12) — the decode hot path's per-layer allreduces are the
    only collectives this knob ever touches."""
    x = x + quantized_psum(attn @ pl["wo"], axis, t, quant,
                           quant_chunk)                        # AR (attn out)
    xn2 = rms_norm(x, pl["ln2"], cfg.norm_eps)
    return x + quantized_psum(mlp_apply(pl, xn2, cfg.activation), axis, t,
                              quant, quant_chunk)              # AR (mlp down)


def _tp_layer_full(cfg, pl, x, positions, mask, axis, heads_t: int,
                   kv_t: int, cache_w=None):
    """One transformer layer over a full sequence.  2 psums when TP-sharded
    (``axis`` set); ``axis=None`` runs the same math full-width."""
    B, S, _ = x.shape
    xn = rms_norm(x, pl["ln1"], cfg.norm_eps)
    q, k, v = _tp_layer_qkv(cfg, pl, xn, positions, heads_t, kv_t)
    attn = gqa_attention(q, k, v, mask).reshape(B, S, heads_t * cfg.head_dim)
    x = _tp_layer_out(cfg, pl, x, attn, axis)
    cache = None
    if cache_w is not None:
        from repro.models.blocks import build_ring_cache
        cache = build_ring_cache(k, v, cache_w)
    return x, cache


def _cp_layer_full(cfg, pl, x, positions, mask, c: int, axis, heads_t: int,
                   kv_t: int, cache_w=None):
    """One transformer layer of a context-parallel prefill (DESIGN.md §9):
    x is this worker's [B, S/c, h] sequence shard, ``positions`` its
    absolute positions and ``mask`` the shard-offset causal [S/c, S] mask.
    The K/V blocks ring-rotate around the "cp" axis (2·(c-1)
    collective-permutes) so attention covers the full sequence in absolute
    order — the monolithic layer's math, token for token.  TP psums
    (``axis``) compose unchanged; the optional ring cache is built from
    the assembled full-sequence K/V, identical on every cp worker."""
    B, s_loc, _ = x.shape
    xn = rms_norm(x, pl["ln1"], cfg.norm_eps)
    q, k, v = _tp_layer_qkv(cfg, pl, xn, positions, heads_t, kv_t)
    kf = ring_kv_assemble(k, "cp", c)
    vf = ring_kv_assemble(v, "cp", c)
    attn = gqa_attention(q, kf, vf, mask).reshape(B, s_loc,
                                                  heads_t * cfg.head_dim)
    x = _tp_layer_out(cfg, pl, x, attn, axis)
    cache = None
    if cache_w is not None:
        from repro.models.blocks import build_ring_cache
        cache = build_ring_cache(kf, vf, cache_w)
    return x, cache


def _tp_layer_step(cfg, pl, x, pos, cache, axis, heads_t: int, kv_t: int,
                   t: int = 1, quant: str = None,
                   quant_chunk: int = DEFAULT_QUANT_CHUNK):
    """One decode step against a ring cache.  2 psums when TP-sharded —
    quantized two-steps instead when ``quant`` is set (DESIGN.md §12).
    ``pos`` is a scalar (shared depth) or [B] per-sequence positions."""
    B = x.shape[0]
    w = cache["k"].shape[1]
    positions = decode_positions(pos, B)
    xn = rms_norm(x, pl["ln1"], cfg.norm_eps)
    q, k, v = _tp_layer_qkv(cfg, pl, xn, positions, heads_t, kv_t)
    ck, cv = ring_cache_update(cache["k"], cache["v"], k, v, pos)
    mask = decode_attn_mask(w, pos, cfg.sliding_window)
    attn = gqa_attention(q, ck, cv, mask).reshape(B, 1,
                                                  heads_t * cfg.head_dim)
    out = _tp_layer_out(cfg, pl, x, attn, axis, t, quant, quant_chunk)
    return out, {"k": ck, "v": cv}


def _tp_layer_paged(cfg, pl, x, pos, cache, layer, bt, axis, heads_t: int,
                    kv_t: int):
    """One transformer layer of a *paged* pass: x [B, S, h] is a prefill
    chunk (S > 1) or a decode token (S == 1) starting at per-sequence
    positions ``pos`` [B]; K/V rows are scattered into layer ``layer`` of
    the whole [L, P, ps, kv_t, D] page pools at the pages ``bt`` names and
    the logical view is gathered back for attention (DESIGN.md §8).  The
    collective schedule is exactly the contiguous layer's: 2 psums when
    TP-sharded — paging is data movement, not communication."""
    B, S = x.shape[:2]
    positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    xn = rms_norm(x, pl["ln1"], cfg.norm_eps)
    q, k, v = _tp_layer_qkv(cfg, pl, xn, positions, heads_t, kv_t)
    ck = paged_layer_write(cache["k"], layer, k, pos, bt)
    cv = paged_layer_write(cache["v"], layer, v, pos, bt)
    kg = paged_layer_gather(ck, layer, bt)
    vg = paged_layer_gather(cv, layer, bt)
    mask = paged_attn_mask(kg.shape[1], pos, S)
    attn = gqa_attention(q, kg, vg, mask).reshape(B, S,
                                                  heads_t * cfg.head_dim)
    return _tp_layer_out(cfg, pl, x, attn, axis), {"k": ck, "v": cv}


def _tp_layers_paged(cfg, blocks, x, pos, cache, bt, axis, heads_t: int,
                     kv_t: int, unroll: bool):
    """Every layer of ``blocks`` for one paged pass.  The whole page pools
    are threaded from layer to layer and indexed by layer, never sliced
    per layer or re-stacked, so a donated pool is updated in place: the
    unrolled loop passes them on, the scan carries them beside the layer
    index and scans only the parameters."""
    if unroll:
        for l in range(jax.tree.leaves(blocks)[0].shape[0]):
            x, cache = _tp_layer_paged(cfg, _layer_slice(blocks, l), x, pos,
                                       cache, l, bt, axis, heads_t, kv_t)
        return x, cache

    def body(carry, pl):
        h, c, l = carry
        h, c = _tp_layer_paged(cfg, pl, h, pos, c, l, bt, axis, heads_t,
                               kv_t)
        return (h, c, l + 1), None

    (x, cache, _), _ = jax.lax.scan(
        body, (x, cache, jnp.zeros((), jnp.int32)), blocks)
    return x, cache


def _layer_slice(blocks, l):
    return {k: v[l] for k, v in blocks.items()}


def _mask_pad_vocab(logits, vocab):
    """Mask pad-vocab columns to the *logit dtype's* min.  A hardcoded
    ``jnp.finfo(jnp.float32).min`` (a strongly-typed numpy scalar) would
    promote bf16 logits to f32 — and overflow to -inf if cast back."""
    if vocab is None or vocab >= logits.shape[-1]:
        return logits
    col = jnp.arange(logits.shape[-1])
    return jnp.where(col < vocab, logits, jnp.finfo(logits.dtype).min)


def _logits_allgather(params, x_last, axis: str, vocab: int = None,
                      eps: float = 1e-5):
    """Vocab-sharded logits + all-gather (paper's Gather, XLA-adapted)."""
    xn = rms_norm(x_last, params["final_norm"], eps)
    local = xn @ params["lm_head"]
    logits = jax.lax.all_gather(local, axis, axis=-1, tiled=True)
    return _mask_pad_vocab(logits, vocab)


def _embed_tokens(cfg, params, tokens, axis):
    """Embedding lookup: vocab-parallel psum when TP-sharded (``axis``
    set), plain table lookup full-width otherwise."""
    if axis is not None:
        return _vocab_parallel_embed(params["embed"], tokens, axis)
    return params["embed"][tokens]


def _head(cfg, params, x_last, axis):
    """Logits head on the last hidden state: vocab-sharded + all-gather
    when TP-sharded, dense otherwise."""
    if axis is not None:
        return _logits_allgather(params, x_last, axis, cfg.vocab_size,
                                 cfg.norm_eps)
    xn = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    return _mask_pad_vocab(xn @ params["lm_head"], cfg.vocab_size)


def _cp_last_hidden(x, last, axis_cp: str):
    """Hand the hidden state of absolute position ``last`` — owned by one
    cp shard of the sequence-sharded x [B, S/c, h] — to every worker: the
    owner contributes its row, everyone else zeros, one psum over the cp
    axis (the '+1 allreduce' of ``commodel.cp_comm_ops``)."""
    s_loc = x.shape[1]
    off = jax.lax.axis_index(axis_cp) * s_loc
    li = jnp.clip(last - off, 0, s_loc - 1)
    row = jax.lax.dynamic_slice_in_dim(x, li, 1, axis=1)[:, 0, :]
    owns = (last >= off) & (last < off + s_loc)
    return jax.lax.psum(jnp.where(owns, row, 0), axis_cp)


# ---------------------------------------------------------------------------
# TP engine
# ---------------------------------------------------------------------------


def _auto_axes(n: int) -> tuple:
    """Axis types of every engine mesh: all Auto, so the jit-level
    sharding rules are the same for the TP meshes (``jax.make_mesh``, whose
    default is Explicit) and the PP stage meshes (``Mesh(...)``)."""
    return (AxisType.Auto,) * n


def make_tp_mesh(t: int) -> Mesh:
    return jax.make_mesh((t,), ("tp",), axis_types=_auto_axes(1))


def make_tp_cp_mesh(t: int, c: int = 1) -> Mesh:
    """Mesh for the single-stage engines on the (tp, cp) plane.  Degenerate
    axes are dropped so t=1 or c=1 never leaves a size-1 axis that XLA
    would emit degenerate collectives over; a fully degenerate (1, 1)
    request still needs one named axis for the shard_map plumbing."""
    shape = [s for s in ((t, "tp"), (c, "cp")) if s[0] > 1]
    if not shape:
        shape = [(1, "tp")]
    return jax.make_mesh(tuple(s for s, _ in shape),
                         tuple(n for _, n in shape),
                         axis_types=_auto_axes(len(shape)))


def _tp_axis_of(mesh: Mesh):
    """(t, axis) of a mesh that may or may not carry a 'tp' axis; the axis
    name is None when t == 1 so callers skip degenerate collectives."""
    t = dict(mesh.shape).get("tp", 1)
    return t, ("tp" if t > 1 else None)


def _cache_spec(axis):
    """[L, B, W, kv, D] cache specs with kv heads on ``axis`` (or fully
    replicated for a t=1 engine); the per-stage [L_s, ...] caches use the
    same spec — always cp-replicated, since CP prefill assembles the full
    cache on every worker."""
    return {"k": P(None, None, None, axis, None),
            "v": P(None, None, None, axis, None)}


def _tp_layers_full(cfg, params, x, positions, mask, heads_t, kv_t,
                    cache_w, unroll: bool, axis="tp"):
    """All layers over a full sequence: unrolled (paper parity) or scanned."""
    if unroll:
        caches = []
        for l in range(cfg.num_layers):
            x, c = _tp_layer_full(cfg, _layer_slice(params["blocks"], l), x,
                                  positions, mask, axis, heads_t, kv_t,
                                  cache_w)
            caches.append(c)
        cache = None
        if cache_w is not None:
            cache = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
        return x, cache

    def body(h, pl):
        h, c = _tp_layer_full(cfg, pl, h, positions, mask, axis,
                              heads_t, kv_t, cache_w)
        return h, c

    return jax.lax.scan(body, x, params["blocks"])


def _tp_layers_step(cfg, params, x, pos, cache, heads_t, kv_t, unroll: bool,
                    axis="tp", t: int = 1, quant: str = None,
                    quant_chunk: int = DEFAULT_QUANT_CHUNK):
    """All layers for one decode token against the stacked [L,...] cache."""
    if unroll:
        new_cache = []
        for l in range(cfg.num_layers):
            x, c = _tp_layer_step(cfg, _layer_slice(params["blocks"], l), x,
                                  pos, _layer_slice(cache, l), axis,
                                  heads_t, kv_t, t, quant, quant_chunk)
            new_cache.append(c)
        return x, jax.tree.map(lambda *xs: jnp.stack(xs), *new_cache)

    def body(h, inp):
        pl, cl = inp
        h, c = _tp_layer_step(cfg, pl, h, pos, cl, axis, heads_t, kv_t,
                              t, quant, quant_chunk)
        return h, c

    return jax.lax.scan(body, x, (params["blocks"], cache))


def _tp_single_step(cfg, params, cache, token, pos, heads_t, kv_t,
                    unroll: bool, axis="tp", t: int = 1, quant: str = None,
                    quant_chunk: int = DEFAULT_QUANT_CHUNK):
    """One full decode step: embed psum + all layers + logits all-gather.
    ``quant`` quantizes ONLY the per-layer psums; the embedding psum and
    the logits all-gather stay full-width (DESIGN.md §12)."""
    x = _embed_tokens(cfg, params, token[:, None], axis)
    x, cache = _tp_layers_step(cfg, params, x, pos, cache, heads_t, kv_t,
                               unroll, axis, t, quant, quant_chunk)
    logits = _head(cfg, params, x[:, 0, :], axis)
    return logits, cache


def tp_prefill(cfg: ModelConfig, mesh: Mesh, cache_w: int = None,
               unroll: bool = True):
    """jit'd fn(params, tokens) -> (logits [B,v], cache|None).

    Collectives per call: (2L+1) allreduce + 1 allgather — Eq. 1 / Table III.
    ``unroll=False`` scans the layer stack (same schedule, O(1)-depth HLO).
    """
    t, axis = _tp_axis_of(mesh)
    heads_t, kv_t = cfg.num_heads // t, cfg.num_kv_heads // t
    specs = tp_param_specs(cfg, tp_axis=axis)

    def fn(params, tokens):
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        mask = make_mask(S, S, window=cfg.sliding_window)
        x = _embed_tokens(cfg, params, tokens, axis)
        x, cache = _tp_layers_full(cfg, params, x, positions, mask,
                                   heads_t, kv_t, cache_w, unroll, axis)
        logits = _head(cfg, params, x[:, -1, :], axis)
        return logits, cache

    out_cache_spec = None if cache_w is None else _cache_spec(axis)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(specs, P(None, None)),
        out_specs=(P(None, None), out_cache_spec),
        check_vma=False))


def cp_prefill(cfg: ModelConfig, mesh: Mesh, cache_w: int = None,
               unroll: bool = True):
    """jit'd fn(params, tokens [B, S], last) -> (logits [B, v], cache|None)
    — the context-parallel prefill (DESIGN.md §9).

    The sequence axis is sharded over the mesh's "cp" axis (S must divide
    by c; the backends pad prompts): every worker embeds and runs each
    layer on its own [B, S/c, h] shard, with the layer's K/V blocks
    ring-exchanged in (c-1) collective-permute rounds
    (``layers.ring_kv_assemble``) so causal attention sees the full
    assembled sequence in absolute order — which keeps the pass
    token-identical to the single-group prefill (softmax reduces in the
    monolithic order; only matmul tiling noise remains).  ``last``
    (traced scalar) names
    the true last prompt position; its hidden state reaches the head via
    one psum over the cp axis.  Per-pass collectives therefore are the
    (2L+1)-allreduce + 1-allgather TP schedule (when t > 1, message rows
    shrunk to the shard) plus ``commodel.cp_comm_ops``: 2L(c-1)
    collective-permutes + 1 cp allreduce.

    The seeded ring cache is assembled FULL on every cp worker (the ring
    already moved every block), so the cache comes out of the shard_map
    replicated over cp and kv-sharded over tp — decode consumes it
    unchanged, which is the whole gather-into-slots handoff.
    """
    t, axis = _tp_axis_of(mesh)
    shape = dict(mesh.shape)
    if "cp" not in shape:
        raise ValueError("cp_prefill needs a mesh with a 'cp' axis "
                         "(make_tp_cp_mesh with c > 1); use tp_prefill "
                         "for c == 1")
    c = shape["cp"]
    heads_t, kv_t = cfg.num_heads // t, cfg.num_kv_heads // t
    specs = tp_param_specs(cfg, tp_axis=axis)

    def fn(params, tokens, last):
        B, s_loc = tokens.shape
        off = jax.lax.axis_index("cp") * s_loc
        positions = jnp.broadcast_to(off + jnp.arange(s_loc), (B, s_loc))
        mask = make_mask(s_loc, c * s_loc, q_offset=off,
                         window=cfg.sliding_window)
        x = _embed_tokens(cfg, params, tokens, axis)
        if unroll:
            caches = []
            for l in range(cfg.num_layers):
                x, cl = _cp_layer_full(cfg, _layer_slice(params["blocks"], l),
                                       x, positions, mask, c, axis, heads_t,
                                       kv_t, cache_w)
                caches.append(cl)
            cache = (jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
                     if cache_w is not None else None)
        else:
            def body(h, pl):
                return _cp_layer_full(cfg, pl, h, positions, mask, c, axis,
                                      heads_t, kv_t, cache_w)

            x, cache = jax.lax.scan(body, x, params["blocks"])
        x_last = _cp_last_hidden(x, last, "cp")
        logits = _head(cfg, params, x_last, axis)
        return logits, cache

    out_cache_spec = None if cache_w is None else _cache_spec(axis)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(specs, P(None, "cp"), P()),
        out_specs=(P(None, None), out_cache_spec),
        check_vma=False))


def tp_decode_step(cfg: ModelConfig, mesh: Mesh, unroll: bool = True,
                   donate: bool = None, vector_pos: bool = False,
                   quant_collectives: str = None,
                   quant_chunk: int = DEFAULT_QUANT_CHUNK):
    """jit'd fn(params, cache, token [B], pos) -> (logits, cache).

    Collectives per call: (2L+1) allreduce + 1 allgather — Table III decode.
    With ``quant_collectives`` ("int8" | "fp8") each of the 2L per-layer
    allreduces lowers to the quantized two-step (DESIGN.md §12): an f32
    amax allreduce of [B, ceil(h/chunk)] + a 1-byte reduce-scatter + a
    1-byte all-gather of [B, h] — so the compiled module shows (2L+1)
    allreduce (2L of them tiny f32 scale exchanges) + 2L reducescatter +
    (2L+1) allgather, exactly ``commodel.comm_ops_for(quant=...)``.  The
    embedding psum and logits gather stay full-width.
    The fast path (``unroll=False``) scans the stacked [L, B, W, kv, D] cache
    and donates it, so XLA aliases the update in place instead of the
    per-layer slice/re-stack copy; ``donate`` overrides that default (the
    paper-parity mode keeps the cache alive for step-by-step comparisons).
    ``vector_pos`` traces ``pos`` as a replicated [B] vector of per-sequence
    positions (the continuous-batching DecodeBackend step) instead of the
    scalar shared position.  On a mesh with a "cp" axis the step runs
    replicated over it — context parallelism is prefill-only (DESIGN.md §9).
    """
    t, axis = _tp_axis_of(mesh)
    quant = _check_quant(quant_collectives)
    heads_t, kv_t = cfg.num_heads // t, cfg.num_kv_heads // t
    specs = tp_param_specs(cfg, tp_axis=axis)
    cache_spec = _cache_spec(axis)
    donate = (not unroll) if donate is None else donate

    def fn(params, cache, token, pos):
        return _tp_single_step(cfg, params, cache, token, pos,
                               heads_t, kv_t, unroll, axis, t, quant,
                               quant_chunk)

    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(specs, cache_spec, P(None),
                  P(None) if vector_pos else P()),
        out_specs=(P(None, None), cache_spec),
        check_vma=False),
        donate_argnums=(1,) if donate else ())


def tp_generate(cfg: ModelConfig, mesh: Mesh, num_tokens: int,
                unroll: bool = False, vector_pos: bool = False,
                quant_collectives: str = None,
                quant_chunk: int = DEFAULT_QUANT_CHUNK):
    """jit'd fn(params, cache, token [B], pos) -> (tokens [B, N], cache).

    Fused greedy multi-token decode: N scanned decode steps run inside ONE
    dispatch via ``lax.fori_loop`` with argmax feedback.  ``tokens[:, i]`` is
    exactly the token a step-by-step ``tp_decode_step`` chain would produce
    after feeding ``token`` at ``pos`` and its successors at ``pos+1 ...``.
    The cache is donated: the [L, B, W, kv, D] buffers are updated in place
    across all N steps without ever being re-materialized on the host.
    ``vector_pos`` takes per-sequence [B] start positions (each sequence
    advances from its own depth — ragged fused decode).
    ``quant_collectives`` lowers the per-layer allreduces to the quantized
    two-step exactly as in ``tp_decode_step`` (DESIGN.md §12).
    """
    t, axis = _tp_axis_of(mesh)
    quant = _check_quant(quant_collectives)
    heads_t, kv_t = cfg.num_heads // t, cfg.num_kv_heads // t
    specs = tp_param_specs(cfg, tp_axis=axis)
    cache_spec = _cache_spec(axis)

    def fn(params, cache, token, pos):
        return greedy_decode_loop(
            lambda c, tok, p: _tp_single_step(cfg, params, c, tok, p,
                                              heads_t, kv_t, unroll, axis,
                                              t, quant, quant_chunk),
            token, cache, pos, num_tokens)

    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(specs, cache_spec, P(None),
                  P(None) if vector_pos else P()),
        out_specs=(P(None, None), cache_spec),
        check_vma=False),
        donate_argnums=(1,))


def tp_paged_step(cfg: ModelConfig, mesh: Mesh, unroll: bool = False,
                  donate: bool = True):
    """jit'd fn(params, cache, tokens [B,S], pos [B], bt [B,n]) ->
    (last-position logits [B, v], cache) — the paged TP pass (DESIGN.md §8).

    ONE builder serves chunked prefill (S = chunk) and paged decode (S = 1);
    each distinct (B, S, n) traces once.  Collectives per call are exactly
    the contiguous step's — (2L+1) allreduce + 1 logits all-gather — for ANY
    chunk length or batch: the page scatter/gather is per-shard local (the
    kv-head axis is the sharded one; the page axis is replicated), so paging
    adds data movement, never communication.  The [L, P, ps, kv/t, D] page
    pools are donated by default (in-place update across chunks and steps).
    """
    t, axis = _tp_axis_of(mesh)
    heads_t, kv_t = cfg.num_heads // t, cfg.num_kv_heads // t
    specs = tp_param_specs(cfg, tp_axis=axis)
    cache_spec = _cache_spec(axis)

    def fn(params, cache, tokens, pos, bt):
        x = _embed_tokens(cfg, params, tokens, axis)
        x, cache = _tp_layers_paged(cfg, params["blocks"], x, pos, cache, bt,
                                    axis, heads_t, kv_t, unroll)
        logits = _head(cfg, params, x[:, -1, :], axis)
        return logits, cache

    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(specs, cache_spec, P(None, None), P(None),
                  P(None, None)),
        out_specs=(P(None, None), cache_spec),
        check_vma=False),
        donate_argnums=(1,) if donate else ())


# ---------------------------------------------------------------------------
# PP engine — one jitted computation per stage, explicit transfers (vLLM-style)
# ---------------------------------------------------------------------------
#
# Real PP serving (the paper's vLLM setup) runs one process group per stage
# and moves activations with NCCL send/recv.  The SPMD-lockstep alternative
# (shard_map over a "pp" axis) would execute every stage's collectives on
# every rank — inflating per-rank counts p×, which is NOT what the paper's
# per-rank profile shows.  So the engine mirrors vLLM: each stage is its own
# jit (optionally TP-sharded over its own device group) and the engine logs
# every inter-stage transfer — that log is our measured Table V / Eq. 2 side.


@dataclasses.dataclass
class TransferRecord:
    phase: str
    count: int          # individual tensors moved (the paper's Send count)
    shape: Tuple[int, ...]
    dtype_bytes: int

    @property
    def bytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return self.count * n * self.dtype_bytes


def stage_layer_range(cfg: ModelConfig, p: int, s: int) -> Tuple[int, int]:
    """Layer interval [lo, hi) owned by stage s.

    An indivisible ``num_layers`` spreads its remainder over the *early*
    stages (``commodel.stage_layer_partition``, which the analytical side
    shares), so every layer is always executed — 28 layers at p=8 runs
    4+4+4+4+3+3+3+3, not 8×3 with four layers silently dropped.
    """
    sizes = stage_layer_partition(cfg.num_layers, p)
    lo = sum(sizes[:s])
    return lo, lo + sizes[s]


class PipelineEngine:
    """Single-request PP (t=1) or hybrid TP×CP×PP (t·c>1) serving engine.

    Stage s owns layers ``stage_layer_range(cfg, p, s)`` on its own
    ``t·c``-device mesh.  Boundary hand-off ships TWO tensors per hop
    (hidden_states + residual, the vLLM pattern) of shape [S, h/t] per TP
    worker, logged in ``self.transfers``.  Within a stage the TP collectives
    (allreduce per row-parallel linear, embedding psum on stage 0, logits
    all-gather on the last stage) are hand-placed and visible in each
    stage's HLO.

    Decode subsystem (DESIGN.md §6): ``prefill_with_cache`` seeds a
    per-stage [L_s, B, W, kv, D] ring KV cache, ``decode_once`` runs one
    token through every stage's jitted decode_step (cache donated on the
    fast path), and ``generate`` drives N greedy tokens through the
    pipeline — every decode boundary hop is a logged [1, h/t]×2
    TransferRecord, the measured side of the paper's Table V decode rows
    and the ``(p−1)·2·(s_d−1)`` term of Eq. 2.

    Context parallelism (``c > 1``, DESIGN.md §9) shards the *prefill*
    sequence axis over each stage's "cp" mesh axis: stage layers run
    ``_cp_layer_full`` (per-layer ring KV exchange), boundary pairs stay
    sequence-sharded on the wire ([S/c, h/t] per worker), and the last
    stage hands the final position's hidden state to the head with one cp
    allreduce — per-stage prefill counts are
    ``commodel.hybrid_stage_collectives(..., c, phase="prefill")``.
    Decode and paged passes run REPLICATED over the cp axis (CP is
    prefill-only): their per-rank collective counts are unchanged at any c.

    ``unroll=False`` scans each stage's layer slice instead of unrolling it
    (same collective schedule, trip-counted in the stage HLO — DESIGN.md §5).
    """

    def __init__(self, cfg: ModelConfig, t: int = 1, p: int = 2,
                 devices=None, unroll: bool = True, c: int = 1,
                 quant_collectives: str = None,
                 quant_chunk: int = DEFAULT_QUANT_CHUNK):
        self.cfg, self.t, self.p, self.c = cfg, t, p, c
        self.unroll = unroll
        # quantized two-step per-layer allreduces on the DECODE path only
        # (DESIGN.md §12) — prefill and paged passes stay full-width
        self.quant = _check_quant(quant_collectives)
        self.quant_chunk = quant_chunk
        devices = devices if devices is not None else jax.devices()
        assert len(devices) >= t * c * p, f"need {t * c * p} devices"
        self.meshes = [self._stage_mesh(devices[s * t * c:(s + 1) * t * c])
                       for s in range(p)]
        # shard_map whenever the stage mesh is non-trivial; a t=1 cp-only
        # stage still needs it for the ring permutes (and decode runs the
        # same fn replicated over cp — all-local, zero collectives)
        self._mapped = t > 1 or c > 1
        self._tp_axis = "tp" if t > 1 else None
        self._param_specs = [self._stage_param_specs(s) for s in range(p)]
        self._stage_cache_spec = _cache_spec(self._tp_axis)
        self.transfers: list = []
        self._stage_fns = [self._build_stage(s) for s in range(p)]
        self._cache_stage_fns = {}      # cache_w -> per-stage prefill fns
        self._decode_stage_fns = {}     # vector_pos -> per-stage decode fns
        self._paged_stage_fns = None    # per-stage paged chunk/decode fns

    def _stage_mesh(self, devs) -> Mesh:
        t, c = self.t, self.c
        axes = [a for a in ((t, "tp"), (c, "cp")) if a[0] > 1]
        if not axes:
            axes = [(1, "tp")]
        return Mesh(np.asarray(devs).reshape([s for s, _ in axes]),
                    tuple(n for _, n in axes),
                    axis_types=_auto_axes(len(axes)))

    # -- shared stage fragments (traced inside each stage's jit) -----------
    def _boundary_in(self, x_or_tokens):
        """Merge a received (hidden, residual) pair; t>1 first redistributes
        the h/t shards among the stage's TP workers (2 all-gathers).  A
        cp-sharded prefill pair stays sequence-sharded — no cp collective."""
        h1, h2 = x_or_tokens
        if self.t > 1:
            h1 = jax.lax.all_gather(h1, "tp", axis=-1, tiled=True)
            h2 = jax.lax.all_gather(h2, "tp", axis=-1, tiled=True)
        return h1 + h2

    def _boundary_out(self, x):
        """Split into the (hidden, residual)-like summand pair for the wire;
        t>1 ships only this worker's h/t shard."""
        t, h = self.t, self.cfg.d_model
        if t > 1:
            idx = jax.lax.axis_index("tp")
            x = jax.lax.dynamic_slice_in_dim(x, idx * (h // t), h // t,
                                             axis=-1)
        return x * 0.25, x * 0.75

    def _head_out(self, params, x_last):
        return _head(self.cfg, params, x_last, self._tp_axis)

    def _stage_keys(self, s: int) -> tuple:
        """Parameter groups stage s reads: its layer slice, plus the
        embedding on the first stage and the head on the last."""
        return (("blocks",) + (("embed",) if s == 0 else ())
                + (("final_norm", "lm_head") if s == self.p - 1 else ()))

    def _stage_param_specs(self, s: int) -> dict:
        full = tp_param_specs(self.cfg, tp_axis=self._tp_axis)
        return {k: full[k] for k in self._stage_keys(s)}

    def _boundary_pair_spec(self, seq_shard: bool = False):
        """Sharding of the two-tensor [B, S|1, h/t] boundary pair;
        ``seq_shard`` marks a cp-sharded prefill pair (sequence axis on
        "cp") — decode/paged pairs are cp-replicated."""
        seq = "cp" if (seq_shard and self.c > 1) else None
        return (P(None, seq, self._tp_axis),) * 2

    def _boundary_specs(self, s: int, seq_shard: bool = False):
        first, last = s == 0, s == self.p - 1
        pair = self._boundary_pair_spec(seq_shard)
        tok = P(None, "cp" if (seq_shard and self.c > 1) else None)
        in_x = tok if first else pair
        out = P(None, None) if last else pair
        return in_x, out

    # -- per-stage jitted computations -------------------------------------
    def _build_stage(self, s: int, cache_w: int = None):
        """Full-sequence stage fn; with ``cache_w`` it also emits the
        stage's seeded [L_s, B, W, kv, D] ring cache.  With c>1 the stage
        runs the CP prefill branch: x sequence-sharded over "cp", per-layer
        ring KV exchange, and an extra traced ``last`` argument naming the
        true last prompt position for the head (DESIGN.md §9)."""
        cfg, t, c, p = self.cfg, self.t, self.c, self.p
        lo, hi = stage_layer_range(cfg, p, s)
        heads_t, kv_t = cfg.num_heads // t, cfg.num_kv_heads // t
        axis = self._tp_axis
        mesh = self.meshes[s]
        first, last_stage = s == 0, s == p - 1

        def fn(params, x_or_tokens, last=None):
            x = (_embed_tokens(cfg, params, x_or_tokens, axis) if first
                 else self._boundary_in(x_or_tokens))
            B, s_loc = x.shape[:2]
            if c > 1:
                off = jax.lax.axis_index("cp") * s_loc
                positions = jnp.broadcast_to(off + jnp.arange(s_loc),
                                             (B, s_loc))
                mask = make_mask(s_loc, c * s_loc, q_offset=off,
                                 window=cfg.sliding_window)
                layer = lambda pl, h: _cp_layer_full(
                    cfg, pl, h, positions, mask, c, axis, heads_t, kv_t,
                    cache_w)
            else:
                positions = jnp.broadcast_to(jnp.arange(s_loc), (B, s_loc))
                mask = make_mask(s_loc, s_loc, window=cfg.sliding_window)
                layer = lambda pl, h: _tp_layer_full(
                    cfg, pl, h, positions, mask, axis, heads_t, kv_t,
                    cache_w)
            if self.unroll:
                caches = []
                for l in range(hi - lo):
                    x, cl = layer(_layer_slice(params["blocks"], l), x)
                    caches.append(cl)
                cache = (jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
                         if cache_w is not None else None)
            else:
                def body(h, pl):
                    return layer(pl, h)

                x, cache = jax.lax.scan(body, x, params["blocks"])
            if last_stage:
                x_last = (_cp_last_hidden(x, last, "cp") if c > 1
                          else x[:, -1, :])
                out = self._head_out(params, x_last)
            else:
                out = self._boundary_out(x)
            return out if cache_w is None else (out, cache)

        if c > 1:
            # uniform (params, x, last) signature across stages keeps the
            # driver simple; non-last stages ignore ``last``
            stage_fn = fn
        else:
            stage_fn = lambda params, x_or_tokens: fn(params, x_or_tokens)
        in_x_spec, out_spec = self._boundary_specs(s, seq_shard=True)
        full_out = (out_spec if cache_w is None
                    else (out_spec, self._stage_cache_spec))
        extra_in = (P(),) if c > 1 else ()
        if self._mapped:
            mapped = jax.shard_map(stage_fn, mesh=mesh,
                                   in_specs=(self._param_specs[s], in_x_spec)
                                   + extra_in,
                                   out_specs=full_out, check_vma=False)
        else:
            mapped = stage_fn               # single-device stage
        return jax.jit(mapped), mesh

    def _build_decode_stage(self, s: int, vector_pos: bool = False):
        """One-token stage fn against the stage's donated ring cache.
        ``vector_pos`` traces ``pos`` as a replicated [B] per-sequence
        vector (continuous batching) instead of the scalar shared depth.
        With c>1 the step runs replicated over the cp axis (CP is
        prefill-only): all specs are cp-unsharded and the per-rank
        collective counts are the c=1 stage's."""
        cfg, t, p = self.cfg, self.t, self.p
        lo, hi = stage_layer_range(cfg, p, s)
        heads_t, kv_t = cfg.num_heads // t, cfg.num_kv_heads // t
        axis = self._tp_axis
        mesh = self.meshes[s]
        first, last = s == 0, s == p - 1

        def fn(params, cache, x_or_tokens, pos):
            x = (_embed_tokens(cfg, params, x_or_tokens[:, None], axis)
                 if first else self._boundary_in(x_or_tokens))
            if self.unroll:
                new_cache = []
                for i in range(hi - lo):
                    x, c = _tp_layer_step(
                        cfg, _layer_slice(params["blocks"], i), x, pos,
                        _layer_slice(cache, i), axis, heads_t, kv_t,
                        t, self.quant, self.quant_chunk)
                    new_cache.append(c)
                cache = jax.tree.map(lambda *xs: jnp.stack(xs), *new_cache)
            else:
                def body(h, inp):
                    pl, cl = inp
                    h, c = _tp_layer_step(cfg, pl, h, pos, cl, axis,
                                          heads_t, kv_t, t, self.quant,
                                          self.quant_chunk)
                    return h, c

                x, cache = jax.lax.scan(
                    body, x, (params["blocks"], cache))
            out = (self._head_out(params, x[:, 0, :]) if last
                   else self._boundary_out(x))
            return out, cache

        _, out_spec = self._boundary_specs(s)
        in_x_spec = P(None) if first else self._boundary_pair_spec()
        pos_spec = P(None) if vector_pos else P()
        if self._mapped:
            mapped = jax.shard_map(
                fn, mesh=mesh,
                in_specs=(self._param_specs[s], self._stage_cache_spec,
                          in_x_spec, pos_spec),
                out_specs=(out_spec, self._stage_cache_spec),
                check_vma=False)
        else:
            mapped = fn
        # fast path donates the cache (in-place update); paper-parity mode
        # keeps it alive for step-by-step comparisons — same convention as
        # tp_decode_step.
        donate = () if self.unroll else (1,)
        return jax.jit(mapped, donate_argnums=donate), mesh

    def _build_paged_stage(self, s: int):
        """Paged stage fn (DESIGN.md §8): fn(params, cache, x_or_tokens,
        pos [B], bt [B, n]) -> (out, cache) against the stage's donated
        [L_s, P, ps, kv/t, D] page pools.  One fn per stage serves every
        chunk length AND paged decode (each distinct shape traces once);
        the per-pass collective schedule is identical to the contiguous
        decode stage — ``commodel.hybrid_stage_collectives`` — because the
        page scatter/gather is shard-local."""
        cfg, t, p = self.cfg, self.t, self.p
        heads_t, kv_t = cfg.num_heads // t, cfg.num_kv_heads // t
        axis = self._tp_axis
        first, last = s == 0, s == p - 1

        def fn(params, cache, x_or_tokens, pos, bt):
            x = (_embed_tokens(cfg, params, x_or_tokens, axis) if first
                 else self._boundary_in(x_or_tokens))
            x, cache = _tp_layers_paged(cfg, params["blocks"], x, pos, cache,
                                        bt, axis, heads_t, kv_t, self.unroll)
            out = (self._head_out(params, x[:, -1, :]) if last
                   else self._boundary_out(x))
            return out, cache

        _, out_spec = self._boundary_specs(s)
        in_x_spec = (P(None, None) if first
                     else self._boundary_pair_spec())
        if self._mapped:
            mapped = jax.shard_map(
                fn, mesh=self.meshes[s],
                in_specs=(self._param_specs[s], self._stage_cache_spec,
                          in_x_spec, P(None), P(None, None)),
                out_specs=(out_spec, self._stage_cache_spec),
                check_vma=False)
        else:
            mapped = fn
        donate = () if self.unroll else (1,)
        return jax.jit(mapped, donate_argnums=donate), self.meshes[s]

    def _paged_fns(self):
        if self._paged_stage_fns is None:
            self._paged_stage_fns = [self._build_paged_stage(s)
                                     for s in range(self.p)]
        return self._paged_stage_fns

    def _cache_fns(self, cache_w: int):
        if cache_w not in self._cache_stage_fns:
            self._cache_stage_fns[cache_w] = [
                self._build_stage(s, cache_w=cache_w) for s in range(self.p)]
        return self._cache_stage_fns[cache_w]

    def _decode_fns(self, vector_pos: bool = False):
        if vector_pos not in self._decode_stage_fns:
            self._decode_stage_fns[vector_pos] = [
                self._build_decode_stage(s, vector_pos=vector_pos)
                for s in range(self.p)]
        return self._decode_stage_fns[vector_pos]

    # -- driver --------------------------------------------------------------
    def prepare(self, params):
        """Place each stage's share of a ``Model.init`` pytree on its own
        mesh: its layer slice, plus the embedding (first stage) and the
        head (last stage) — no stage holds the whole model."""
        staged = []
        for s, mesh in enumerate(self.meshes):
            lo, hi = stage_layer_range(self.cfg, self.p, s)
            part = {k: params[k] for k in self._stage_keys(s)}
            part["blocks"] = jax.tree.map(lambda a: a[lo:hi],
                                          params["blocks"])
            staged.append(place_params(part, self._param_specs[s], mesh))
        return staged

    def _move_boundary(self, out, s: int, phase: str, log: bool = True,
                       seq_shard: bool = False):
        """Ship the two-tensor boundary pair to stage s+1 (device_put,
        DESIGN.md §3) and log one TransferRecord per tensor.  ``seq_shard``
        marks a cp-sharded prefill pair: each worker then carries only its
        [S/c, h/t] block, which is what the record charges."""
        nxt = self.meshes[s + 1]
        spec = self._boundary_pair_spec(seq_shard)[0]
        moved = tuple(jax.device_put(h, NamedSharding(nxt, spec))
                      for h in out)
        c = self.c if (seq_shard and self.c > 1) else 1
        if log:
            for h in moved:
                self.transfers.append(TransferRecord(
                    phase, 1,
                    (h.shape[0], h.shape[1] // c, h.shape[-1] // self.t),
                    jnp.dtype(h.dtype).itemsize))
        return moved

    def _prefill_last(self, tokens, last):
        """Validate/default the ``last`` index of a CP prefill pass."""
        S = tokens.shape[1]
        if self.c > 1 and S % self.c:
            raise ValueError(
                f"CP prefill shards the sequence over c={self.c}: pad the "
                f"prompt to a multiple of c (got S={S})")
        return jnp.int32(S - 1 if last is None else last)

    def forward(self, staged_params, tokens, phase: str = "prefill",
                last: int = None):
        """Run one pass; logs (p-1)×2 transfers of [S, h/t] — Eq. 2 / Eq. 7.

        With c>1 the pass is CP-sharded (DESIGN.md §9): S must divide by c
        and ``last`` names the true last prompt position (default S-1) —
        logits come from it, boundary hops carry [S/c, h/t] per worker."""
        extra = (self._prefill_last(tokens, last),) if self.c > 1 else ()
        x = tokens
        for s in range(self.p):
            fn, _ = self._stage_fns[s]
            out = fn(staged_params[s], x, *extra)
            if s < self.p - 1:
                x = self._move_boundary(out, s, phase, seq_shard=True)
            else:
                return out

    def prefill_with_cache(self, staged_params, tokens, cache_w: int,
                           last: int = None):
        """Prefill that seeds every stage's [L_s, B, W, kv, D] ring cache.

        Returns (last-position logits [B, v], per-stage cache list); logs
        the same (p-1)×2 [S, h/t] prefill transfers as ``forward`` ([S/c,
        h/t] per worker under CP, where the seeded caches come out FULL on
        every cp worker thanks to the ring assembly — the gather-into-slots
        handoff, DESIGN.md §9).
        """
        extra = (self._prefill_last(tokens, last),) if self.c > 1 else ()
        fns = self._cache_fns(cache_w)
        x = tokens
        caches = []
        for s in range(self.p):
            fn, _ = fns[s]
            out, cache = fn(staged_params[s], x, *extra)
            caches.append(cache)
            if s < self.p - 1:
                x = self._move_boundary(out, s, "prefill", seq_shard=True)
            else:
                return out, caches

    def decode_once(self, staged_params, caches, token, pos):
        """One pipelined decode step: token [B] in, next-token logits out.

        Each stage runs its jitted decode_step against its own cache; every
        boundary ships the two-tensor [1, h/t] pair logged with
        phase="decode" — the measured Table V decode rows.  ``pos`` may be a
        scalar or a [B] vector of per-sequence positions (continuous
        batching).  Returns (logits [B, v], new per-stage caches); on the
        fast path the input caches are donated (consumed).
        """
        pos = jnp.asarray(pos, jnp.int32)
        fns = self._decode_fns(vector_pos=pos.ndim > 0)
        # next-token feedback hop to stage 0 (a few bytes; not charged by
        # Eq. 2, which counts only the boundary activation tensors)
        x = jax.device_put(token, NamedSharding(self.meshes[0], P(None)))
        new_caches = []
        out = None
        for s in range(self.p):
            fn, _ = fns[s]
            out, c = fn(staged_params[s], caches[s], x, pos)
            new_caches.append(c)
            if s < self.p - 1:
                x = self._move_boundary(out, s, "decode")
        return out, new_caches

    def paged_pass(self, staged_params, caches, tokens, pos, bt,
                   phase: str = "decode"):
        """One paged pass through all p stages: a prefill chunk
        (tokens [B, S], phase="prefill") or a paged decode step
        (tokens [B, 1], phase="decode") — DESIGN.md §8.

        Every boundary ships the same two-tensor [B, S, h/t] summand pair as
        the contiguous passes, logged with ``phase`` — so per-chunk prefill
        hops and per-step decode hops stay separately assertable against
        ``commodel.chunked_prefill_ops`` / the decode send rows.  Returns
        (last-position logits [B, v], new per-stage page pools); on the fast
        path the input pools are donated (consumed).
        """
        fns = self._paged_fns()
        pos = jnp.asarray(pos, jnp.int32)
        bt = jnp.asarray(bt, jnp.int32)
        x = jax.device_put(jnp.asarray(tokens, jnp.int32),
                           NamedSharding(self.meshes[0], P(None, None)))
        new_caches = []
        out = None
        for s in range(self.p):
            fn, _ = fns[s]
            out, c = fn(staged_params[s], caches[s], x, pos, bt)
            new_caches.append(c)
            if s < self.p - 1:
                x = self._move_boundary(out, s, phase)
        return out, new_caches

    # -- instruction-queue surface (runtime/schedule.py, DESIGN.md §11) ------
    def decode_stage_fns(self, vector_pos: bool = False):
        """The per-stage jitted decode fns, independently drivable: the
        dynamic instruction queue issues them one stage at a time instead
        of through the fused ``decode_once`` wave."""
        return [fn for fn, _ in self._decode_fns(vector_pos=vector_pos)]

    def paged_stage_fns(self):
        """Per-stage paged fns for queue-driven paged decode rounds."""
        return [fn for fn, _ in self._paged_fns()]

    def feed_tokens(self, tokens, paged: bool = False):
        """Place next-token ids on stage 0's mesh — the feedback hop that
        starts a decode round (a few bytes; not charged by Eq. 2)."""
        spec = P(None, None) if paged else P(None)
        return jax.device_put(jnp.asarray(tokens, jnp.int32),
                              NamedSharding(self.meshes[0], spec))

    def send_boundary(self, out, s: int, phase: str = "decode"):
        """Ship stage ``s``'s boundary pair to stage ``s+1`` and log its
        TransferRecords — the ``BoundarySend``/``BoundaryRecv`` pair of an
        instruction-queue round."""
        return self._move_boundary(out, s, phase)

    def generate(self, staged_params, caches, token, pos, num_tokens: int):
        """Greedy pipelined generation: N tokens through all p stages.

        The argmax feedback loop is the shared driver
        (``models.transformer.greedy_decode_host_loop``), so ``out[:, i]``
        equals what a chain of decode_once + argmax would emit — and, token
        for token, what ``tp_generate`` / ``InferenceEngine`` produce from
        the same params.  Logs (p-1)·2·N decode transfers: with the prefill
        token counted, exactly the paper's (p−1)·2·(s_d−1) for s_d = N+1.
        Returns (tokens [B, N] int32, final per-stage caches).
        """
        state = {"caches": caches}

        def step(tok, pos_i):
            logits, state["caches"] = self.decode_once(
                staged_params, state["caches"], tok, pos_i)
            return logits

        out = greedy_decode_host_loop(step, token, pos, num_tokens)
        return out, state["caches"]

    # -- introspection -------------------------------------------------------
    def stage_hlo(self, staged_params, tokens, s: int,
                  last: int = None) -> str:
        """Compiled HLO of stage s's prefill (collective-count validation);
        under CP the counts include the stage's ring permutes —
        ``commodel.hybrid_stage_collectives(..., c, phase="prefill")``."""
        extra = (self._prefill_last(tokens, last),) if self.c > 1 else ()
        x = tokens
        for i in range(s):
            fn, _ = self._stage_fns[i]
            out = fn(staged_params[i], x, *extra)
            x = self._move_boundary(out, i, "hlo", log=False,
                                    seq_shard=True)
        fn, _ = self._stage_fns[s]
        return fn.lower(staged_params[s], x, *extra).compile().as_text()

    def stage_decode_hlo(self, staged_params, caches, token, pos,
                         s: int) -> str:
        """Compiled HLO of stage s's decode_step — asserted against
        ``commodel.hybrid_stage_collectives``.  Earlier stages run on cache
        copies so the caller's caches survive donation."""
        fns = self._decode_fns()
        pos = jnp.int32(pos)
        x = jax.device_put(token, NamedSharding(self.meshes[0], P(None)))
        for i in range(s):
            fn, _ = fns[i]
            out, _ = fn(staged_params[i],
                        jax.tree.map(jnp.copy, caches[i]), x, pos)
            x = self._move_boundary(out, i, "hlo", log=False)
        fn, _ = fns[s]
        return fn.lower(staged_params[s], caches[s], x,
                        pos).compile().as_text()

    def stage_paged_hlo(self, staged_params, caches, tokens, pos, bt,
                        s: int) -> str:
        """Compiled HLO of stage s's paged pass (any chunk length) —
        asserted against ``commodel.hybrid_stage_collectives``, which covers
        paged passes too (counts are chunk-length-invariant).  Earlier
        stages run on cache copies so the caller's pools survive donation."""
        fns = self._paged_fns()
        pos = jnp.asarray(pos, jnp.int32)
        bt = jnp.asarray(bt, jnp.int32)
        x = jax.device_put(jnp.asarray(tokens, jnp.int32),
                           NamedSharding(self.meshes[0], P(None, None)))
        for i in range(s):
            fn, _ = fns[i]
            out, _ = fn(staged_params[i],
                        jax.tree.map(jnp.copy, caches[i]), x, pos, bt)
            x = self._move_boundary(out, i, "hlo", log=False)
        fn, _ = fns[s]
        return fn.lower(staged_params[s], caches[s], x, pos,
                        bt).compile().as_text()

    def transfer_summary(self, phase: str = None):
        """Aggregate logged transfers; ``phase`` filters to one phase so the
        decode rows can be asserted against pp/hybrid_comm_ops directly."""
        recs = [r for r in self.transfers if phase in (None, r.phase)]
        return {"count": sum(r.count for r in recs),
                "bytes": sum(r.bytes for r in recs)}
