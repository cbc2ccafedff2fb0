"""Shared neural-net layers: norms, RoPE, masks, attention, GLU MLPs.

Everything is pure-functional: ``init_*`` builds param pytrees, ``*_apply``
consumes them.  Attention dispatches to the Pallas flash kernels on TPU and to
the pure-jnp reference elsewhere (see ``repro.kernels``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(rng, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, D]; positions: [..., S] int32."""
    d = x.shape[-1]
    half = d // 2
    freq = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half))
    # positions [..., S] -> angles [..., S, 1, half] broadcasting over heads
    angles = positions[..., :, None, None].astype(jnp.float32) * freq
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------


import dataclasses


@dataclasses.dataclass(frozen=True)
class MaskSpec:
    """Lazy attention-mask description — the chunked (flash-style) attention
    path builds per-KV-block masks on the fly instead of materializing the
    [S, S] boolean (1 GiB at 32k), which is itself part of the §Perf win."""

    mode: str = "causal"         # causal | bidirectional | prefix
    window: Optional[int] = None
    prefix_len: int = 0
    q_offset: int = 0

    def materialize(self, q_len: int, kv_len: int):
        return make_mask(q_len, kv_len, mode=self.mode,
                         q_offset=self.q_offset, window=self.window,
                         prefix_len=self.prefix_len)

    def block(self, q_pos, kv_pos):
        """Mask for explicit position vectors: [len(q_pos), len(kv_pos)]."""
        qp = q_pos[:, None]
        kp = kv_pos[None, :]
        if self.mode == "bidirectional":
            m = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
        elif self.mode == "prefix":
            m = (kp <= qp) | (kp < self.prefix_len)
        else:
            m = kp <= qp
        if self.window is not None:
            m &= kp > qp - self.window
        return m


def make_mask(q_len: int, kv_len: int, *, mode: str = "causal",
              q_offset=0, window=None, prefix_len: int = 0):
    """Boolean [q_len, kv_len] mask (True = attend).

    mode: "causal" | "bidirectional" | "prefix" (bidirectional prefix + causal
    suffix, PaliGemma-style).  ``window`` adds a sliding-window constraint.
    """
    q_pos = q_offset + jnp.arange(q_len)[:, None]
    kv_pos = jnp.arange(kv_len)[None, :]
    if mode == "bidirectional":
        mask = jnp.ones((q_len, kv_len), bool)
    elif mode == "prefix":
        mask = (kv_pos <= q_pos) | (kv_pos < prefix_len)
    else:
        mask = kv_pos <= q_pos
    if window is not None:
        mask &= kv_pos > q_pos - window
    return mask


def decode_cache_mask(cache_len: int, pos, window=None):
    """Valid-slot mask for a (possibly ring-buffer) KV cache.

    With a ring buffer of width W == window, every slot is valid once pos > W;
    before that only the first ``pos`` slots are.  ``pos`` may be a scalar
    (shared decode position, mask [cache_len]) or a [B] vector of per-sequence
    positions (continuous batching, mask [B, cache_len]).
    """
    idx = jnp.arange(cache_len)
    p = jnp.asarray(pos)[..., None]
    mask = idx < p
    if window is not None:
        mask = mask | (p > cache_len)
    return mask


def decode_positions(pos, batch: int):
    """RoPE position tensor [B, 1] for one decode step from a scalar or [B]
    position; the scalar form broadcasts one shared position over the batch."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return jnp.full((batch, 1), pos, jnp.int32)
    return pos[:, None]


def decode_attn_mask(cache_len: int, pos, window=None):
    """`gqa_attention`-broadcastable cache mask for one decode step: [1, W]
    for a scalar position, [B, 1, 1, 1, W] (per-sequence) for pos [B]."""
    m = decode_cache_mask(cache_len, pos + 1, window)
    if jnp.ndim(pos) == 0:
        return m[None, :]
    return m[:, None, None, None, :]


def paged_slots(pos, q_len: int, block_table, page_size: int):
    """Physical (page, row-in-page) of a chunk's logical positions.

    pos: [B] start positions; block_table: [B, n] int32 — logical page j of
    sequence b lives at physical page ``block_table[b, j]``.  Logical
    position q maps to row ``q % page_size`` of page
    ``block_table[b, q // page_size]``.  Returns (page, off), both [B, S].
    Every live page is owned by exactly one sequence (runtime/kvpool.py),
    so the destinations are distinct — except for the reserved scratch
    page 0, which inactive slots alias on purpose (their garbage writes
    must land somewhere harmless).
    """
    lp = pos[:, None] + jnp.arange(q_len)[None, :]         # [B, S] logical
    page = jnp.take_along_axis(block_table, lp // page_size, axis=1)
    return page, lp % page_size


def paged_layer_write(pool, layer, x, pos, block_table):
    """Write a chunk's K or V rows x [B, S, Hkv, D] into layer ``layer`` of
    the whole [L, P, ps, Hkv, D] page pool, at the pages the block table
    names (``paged_slots``).  One scatter on the full pool: with the pool
    donated and carried through the layer loop, XLA updates it in place
    (DESIGN.md §8).  ``layer`` may be a traced scalar."""
    page, off = paged_slots(pos, x.shape[1], block_table, pool.shape[2])
    return pool.at[layer, page, off].set(x)


def paged_layer_gather(pool, layer, block_table):
    """The logical KV view of layer ``layer`` named by a block table:
    pool [L, P, ps, Hkv, D] + table [B, n] -> [B, n*ps, Hkv, D].  Row
    j*ps+r of the result is logical position j*ps+r of sequence b; entries
    past the sequence's length alias whatever page the table names there
    (scratch page 0 for unallocated blocks) and must be masked by the
    caller."""
    B, n = block_table.shape
    _, _, ps, Hkv, D = pool.shape
    return pool[layer, block_table].reshape(B, n * ps, Hkv, D)


def paged_attn_mask(kv_len: int, pos, q_len: int):
    """[B, 1, 1, S, T] causal mask for a paged chunk step: query s of
    sequence b sits at absolute position pos[b]+s and may attend to logical
    KV positions <= it (which covers both the previously-cached prefix and
    the chunk's own causal triangle — the pages were just updated in place)."""
    q_pos = jnp.asarray(pos)[:, None] + jnp.arange(q_len)[None, :]  # [B, S]
    kv_pos = jnp.arange(kv_len)
    m = kv_pos[None, None, :] <= q_pos[:, :, None]                  # [B, S, T]
    return m[:, None, None, :, :]


def ring_kv_assemble(blk, axis: str, c: int):
    """Ring all-gather of per-shard K or V blocks over the ``axis`` mesh
    axis, assembled in ABSOLUTE sequence order (DESIGN.md §9).

    ``blk`` is this context-parallel worker's [B, S/c, Hkv, D] block of a
    sequence sharded over c workers; after c-1 ``ppermute`` rounds — each
    worker forwards the block it received last round to its ring successor
    — every worker holds the full [B, S, Hkv, D] tensor, with the block
    that originated on worker r at rows [r·S/c, (r+1)·S/c).  Because the
    assembly is in absolute order, the assembled K/V is *bitwise* the
    monolithic pass's and attention softmax-reduces over it in the same
    order — CP prefill differs from the single-group path only by matmul
    tiling noise (~1e-6, never a greedy-argmax flip), where the
    overlap-friendly online-softmax formulation of ring attention would
    reorder the reduction itself.

    Communication: c-1 collective-permutes per call; a layer calls this
    twice (K and V), giving the 2·L·(c-1) ring rows of
    ``commodel.cp_comm_ops``.  Must run inside shard_map with ``axis`` in
    the mesh.
    """
    idx = jax.lax.axis_index(axis)
    s_loc = blk.shape[1]
    full = jnp.zeros(blk.shape[:1] + (c * s_loc,) + blk.shape[2:], blk.dtype)
    perm = [(i, (i + 1) % c) for i in range(c)]
    cur = blk
    for step in range(c):
        src = (idx - step) % c
        full = jax.lax.dynamic_update_slice_in_dim(full, cur, src * s_loc,
                                                   axis=1)
        if step < c - 1:
            cur = jax.lax.ppermute(cur, axis, perm)
    return full


def ring_cache_update(cache_k, cache_v, k, v, pos):
    """Write this step's K/V row into slot ``pos % W`` of a ring cache.

    cache_k/v: [B, W, Hkv, D]; k/v: [B, 1, Hkv, D].  A scalar ``pos`` keeps
    the seed ``dynamic_update_slice`` (all sequences share one slot — XLA
    aliases the donated buffer); a [B] vector scatters one row per sequence
    at its own slot, the continuous-batching layout.
    """
    w = cache_k.shape[1]
    if jnp.ndim(pos) == 0:
        slot = pos % w
        return (jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0)),
                jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0)))
    bidx = jnp.arange(cache_k.shape[0])
    slot = pos % w
    return (cache_k.at[bidx, slot].set(k[:, 0]),
            cache_v.at[bidx, slot].set(v[:, 0]))


# ---------------------------------------------------------------------------
# attention (reference path; kernels/ holds the Pallas TPU versions)
# ---------------------------------------------------------------------------


def gqa_attention(q, k, v, mask, *, softcap=None):
    """Grouped-query attention.

    q: [B, S, Hq, D]; k, v: [B, T, Hkv, D]; mask broadcastable to
    [B, Hkv, G, S, T] (usually [S, T]).  Returns [B, S, Hq, D].
    """
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    logits *= 1.0 / np.sqrt(D)
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, Hq, D)


def chunked_gqa_attention(q, k, v, spec: "MaskSpec", *, kv_chunk: int = 1024,
                          softcap=None):
    """Flash-style attention: online softmax over KV chunks (lax.scan), no
    [S, T] score materialization and no [S, T] mask.  Peak activation is
    [B, Hkv, G, S, kv_chunk] — the jnp counterpart of the Pallas flash
    kernel, used by the production forward path on shapes where reference
    attention's S² HBM traffic dominates the roofline (§Perf)."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    kv_chunk = min(kv_chunk, T)
    assert T % kv_chunk == 0, (T, kv_chunk)
    n = T // kv_chunk
    qg = q.reshape(B, S, Hkv, G, D)
    q_pos = spec.q_offset + jnp.arange(S)
    scale = 1.0 / np.sqrt(D)

    kc = k.reshape(B, n, kv_chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n, kv_chunk, Hkv, D).transpose(1, 0, 2, 3, 4)

    def body(carry, inp):
        m_run, l_run, acc = carry
        ci, k_c, v_c = inp                              # [B,C,Hkv,D]
        kv_pos = ci * kv_chunk + jnp.arange(kv_chunk)
        logits = jnp.einsum("bskgd,btkd->bkgst", qg, k_c).astype(jnp.float32)
        logits *= scale
        if softcap is not None:
            logits = jnp.tanh(logits / softcap) * softcap
        mask = spec.block(q_pos, kv_pos)                # [S, C]
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
        m_new = jnp.maximum(m_run, logits.max(-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        l_run = l_run * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(q.dtype), v_c).astype(jnp.float32)
        return (m_new, l_run, acc), None

    init = (jnp.full((B, Hkv, G, S), jnp.finfo(jnp.float32).min, jnp.float32),
            jnp.zeros((B, Hkv, G, S), jnp.float32),
            jnp.zeros((B, Hkv, G, S, D), jnp.float32))
    (m_run, l_run, acc), _ = jax.lax.scan(body, init,
                                          (jnp.arange(n), kc, vc))
    out = acc / jnp.maximum(l_run, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D).astype(q.dtype)


def init_attention(rng, cfg: ModelConfig, num_layers: int, n_heads=None,
                   dtype=None):
    n_heads = n_heads or cfg.num_heads
    dtype = dtype or jnp.dtype(cfg.dtype)
    h, d = cfg.d_model, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(rng, 4)
    L = num_layers
    return {
        "wq": dense_init(kq, (L, h, n_heads * d), dtype),
        "wk": dense_init(kk, (L, h, cfg.num_kv_heads * d), dtype),
        "wv": dense_init(kv, (L, h, cfg.num_kv_heads * d), dtype),
        "wo": dense_init(ko, (L, n_heads * d, h), dtype),
    }


# ---------------------------------------------------------------------------
# GLU / MLP
# ---------------------------------------------------------------------------


def init_mlp(rng, d_model: int, d_ff: int, activation: str, num_layers: int,
             dtype) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    L = num_layers
    p = {
        "w1": dense_init(k1, (L, d_model, d_ff), dtype),
        "w2": dense_init(k2, (L, d_ff, d_model), dtype),
    }
    if activation in ("swiglu", "geglu"):
        p["w3"] = dense_init(k3, (L, d_model, d_ff), dtype)
    return p


def mlp_apply(p, x, activation: str):
    up = x @ p["w1"]
    if activation == "swiglu":
        act = jax.nn.silu(up) * (x @ p["w3"])
    elif activation == "geglu":
        act = jax.nn.gelu(up, approximate=True) * (x @ p["w3"])
    else:
        act = jax.nn.gelu(up, approximate=True)
    return act @ p["w2"]
