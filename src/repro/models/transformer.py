"""Model trunk: embedding + lax.scan over homogeneous blocks + head.

One trunk serves all six assigned families; the per-layer block is selected by
``cfg.family``.  Layers are stacked on a leading [L, ...] axis and scanned,
which keeps the HLO compact enough to compile 512-device SPMD modules on the
CPU host platform (see launch/dryrun.py).

Three entry points:
  forward(params, ...)              full-sequence logits (train / encoder)
  prefill(params, ..., cache_w)     full-sequence logits + seeded KV/state cache
  decode_step(params, cache, ...)   one token against the cache (serve_step)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig
from repro.models import blocks as blocks_lib
from repro.models import hybrid as hybrid_lib
from repro.models import moe as moe_lib
from repro.models import layers as layers_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import MaskSpec, dense_init, make_mask, rms_norm

_BLOCKS = {
    "dense":   (blocks_lib.init_dense_blocks, blocks_lib.dense_block_apply),
    "encoder": (blocks_lib.init_dense_blocks, blocks_lib.dense_block_apply),
    "vlm":     (blocks_lib.init_dense_blocks, blocks_lib.dense_block_apply),
    "moe":     (moe_lib.init_moe_blocks, moe_lib.moe_block_apply),
    "ssm":     (ssm_lib.init_ssm_blocks, ssm_lib.ssm_block_apply),
    "hybrid":  (hybrid_lib.init_hybrid_blocks, hybrid_lib.hybrid_block_apply),
}


def greedy_decode_loop(step_fn, tokens, cache, pos, num_tokens: int):
    """Fused greedy generation: ``num_tokens`` autoregressive steps in one
    ``lax.fori_loop`` (single dispatch when jitted), feeding each argmax back
    in at the next position.  ``step_fn(cache, tok [B], pos_i) -> (logits
    [B, v], cache)`` supplies the single step; shared by ``Model.decode_steps``
    and the explicit-TP ``tp_generate`` so the feedback loop cannot diverge.

    Returns (generated [B, num_tokens] int32, final cache); ``out[:, i]``
    equals what a chain of step + argmax calls would emit.
    """
    B = tokens.shape[0]

    def step(i, carry):
        tok, cache, out = carry
        logits, cache = step_fn(cache, tok, pos + i)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, i))
        return nxt, cache, out

    out = jnp.zeros((B, num_tokens), jnp.int32)
    _, cache, out = jax.lax.fori_loop(0, num_tokens, step,
                                      (tokens, cache, out))
    return out, cache


def greedy_decode_host_loop(step_fn, tokens, pos, num_tokens: int):
    """Host-driven counterpart of :func:`greedy_decode_loop` for engines
    whose step spans multiple dispatches (the per-stage-jit
    ``PipelineEngine``, whose boundary hops are device_put transfers that
    cannot live inside one ``fori_loop``).  ``step_fn(tok [B], pos_i) ->
    logits [B, v]`` supplies the step; the argmax feedback is identical, so
    ``out[:, i]`` matches ``greedy_decode_loop`` token for token on the
    same per-step logits.  Returns generated [B, num_tokens] int32."""
    out = []
    tok = tokens
    for i in range(num_tokens):
        logits = step_fn(tok, pos + i)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    return jnp.stack(out, axis=1)


class Model:
    """Functional model wrapper for one ModelConfig."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.init_blocks, self.block_apply = _BLOCKS[cfg.family]

    # ------------------------------------------------------------- params
    def init(self, rng) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        k_emb, k_blk, k_head = jax.random.split(rng, 3)
        params = {
            "blocks": self.init_blocks(k_blk, cfg, cfg.num_layers, dtype),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }
        # vocab padded to shard cleanly on the model axis; pad logits are
        # masked to -inf in _head and the pad embedding rows start at zero.
        pv = cfg.padded_vocab
        if cfg.family != "encoder":
            emb = dense_init(k_emb, (pv, cfg.d_model), dtype, scale=1.0)
            if pv != cfg.vocab_size:
                emb = emb.at[cfg.vocab_size:].set(0)
            params["embed"] = emb
        if not cfg.tie_embeddings:
            head = dense_init(k_head, (cfg.d_model, pv), dtype)
            if pv != cfg.vocab_size:
                head = head.at[:, cfg.vocab_size:].set(0)
            params["lm_head"] = head
        return params

    # ------------------------------------------------------------ helpers
    def _embed(self, params, tokens, prefix_emb=None, features=None):
        cfg = self.cfg
        if cfg.family == "encoder":
            x = features.astype(jnp.dtype(cfg.dtype))
        else:
            x = params["embed"][tokens]
            if cfg.scale_embedding:
                x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
            if prefix_emb is not None:
                x = jnp.concatenate(
                    [prefix_emb.astype(x.dtype), x], axis=1)
        return x

    def _head(self, params, x):
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings and cfg.family != "encoder":
            logits = x @ params["embed"].T
        else:
            logits = x @ params["lm_head"]
        if cfg.padded_vocab != cfg.vocab_size:
            # mask at the *logit dtype's* min: a hardcoded f32 numpy scalar
            # is strongly typed, promoting bf16 logits to f32 (and f32 min
            # overflows to -inf if later cast back down).
            col = jnp.arange(cfg.padded_vocab)
            logits = jnp.where(col < cfg.vocab_size, logits,
                               jnp.finfo(logits.dtype).min)
        return logits

    def _mask(self, q_len, kv_len, prefix_len=0):
        cfg = self.cfg
        mode = {"encoder": "bidirectional", "vlm": "prefix"}.get(cfg.family,
                                                                 "causal")
        if cfg.attention_impl == "chunked":
            # lazy spec: the chunked path builds per-block masks on the fly
            return MaskSpec(mode=mode, window=cfg.sliding_window,
                            prefix_len=prefix_len)
        return make_mask(q_len, kv_len, mode=mode, window=cfg.sliding_window,
                         prefix_len=prefix_len)

    def cache_width(self, max_len: int) -> int:
        w = self.cfg.sliding_window or max_len
        return min(w, max_len)

    # -------------------------------------------------------------- scans
    def _scan_forward(self, params, x, positions, mask, remat: str = "none"):
        cfg = self.cfg

        def body(carry, p_l):
            h, aux = carry
            y, _, a = self.block_apply(cfg, p_l, h, positions, mask)
            return (y, aux + a), None

        if remat == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        elif remat == "dots":
            body = jax.checkpoint(
                body, prevent_cse=False,
                policy=jax.checkpoint_policies.checkpoint_dots)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["blocks"])
        return x, aux

    def _scan_prefill(self, params, x, positions, mask, cache_w: int):
        cfg = self.cfg

        def body(carry, p_l):
            h, aux = carry
            y, c, a = self.block_apply(cfg, p_l, h, positions, mask,
                                       build_cache_w=cache_w)
            return (y, aux + a), c

        (x, aux), cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                       params["blocks"])
        return x, aux, cache

    def _scan_paged(self, params, x, positions, cache, pos, block_table):
        """The paged layer loop.  The whole [L, P, ps, Hkv, D] pool rides
        in the carry beside the layer index, and only the parameters are
        scanned: each layer scatters its rows into the pool and gathers
        its view from it by index, so a donated pool is updated in place
        — never sliced per layer, re-stacked or copied (DESIGN.md §8)."""
        cfg = self.cfg

        def body(carry, p_l):
            h, aux, pool, l = carry
            y, pool, a = self.block_apply(cfg, p_l, h, positions, None,
                                          cache=pool, pos=pos,
                                          block_table=block_table, layer=l)
            return (y, aux + a, pool, l + 1), None

        (x, aux, cache, _), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32), cache,
                   jnp.zeros((), jnp.int32)), params["blocks"])
        return x, aux, cache

    def _scan_decode(self, params, x, positions, cache, pos):
        cfg = self.cfg

        def body(carry, inp):
            p_l, c_l = inp
            h, aux = carry
            y, c, a = self.block_apply(cfg, p_l, h, positions, None,
                                       cache=c_l, pos=pos)
            return (y, aux + a), c

        (x, aux), new_cache = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (params["blocks"], cache))
        return x, aux, new_cache

    # ---------------------------------------------------------- interface
    def forward(self, params, tokens=None, prefix_emb=None, features=None,
                remat: Optional[str] = None, return_hidden: bool = False):
        """Full-sequence logits.  Returns (logits [B,S,v], aux) — or the
        final normalized hidden states when ``return_hidden`` (used by the
        fused chunked-CE loss, which never materializes [B,S,V])."""
        cfg = self.cfg
        x = self._embed(params, tokens, prefix_emb, features)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        prefix_len = 0 if prefix_emb is None else prefix_emb.shape[1]
        mask = self._mask(S, S, prefix_len)
        x, aux = self._scan_forward(params, x, positions, mask,
                                    remat if remat is not None else cfg.remat)
        if return_hidden:
            return rms_norm(x, params["final_norm"], cfg.norm_eps), aux
        return self._head(params, x), aux

    def head_matrix(self, params):
        """[h, V] output projection (tied or untied)."""
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def prefill(self, params, tokens, max_len: int, prefix_emb=None):
        """Returns (last-position logits [B,v], cache, seq_len_done)."""
        cfg = self.cfg
        x = self._embed(params, tokens, prefix_emb)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        prefix_len = 0 if prefix_emb is None else prefix_emb.shape[1]
        mask = self._mask(S, S, prefix_len)
        x, aux, cache = self._scan_prefill(params, x, positions, mask,
                                           self.cache_width(max_len))
        return self._head(params, x[:, -1:, :])[:, 0], cache, S

    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        L, dtype = cfg.num_layers, jnp.dtype(cfg.dtype)
        if cfg.family == "ssm":
            return ssm_lib.init_ssm_cache(cfg, L, batch, dtype)
        w = self.cache_width(max_len)
        if cfg.family == "hybrid":
            return hybrid_lib.init_hybrid_cache(cfg, L, batch, w, dtype)
        return {
            "k": jnp.zeros((L, batch, w, cfg.num_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((L, batch, w, cfg.num_kv_heads, cfg.head_dim), dtype),
        }

    def init_paged_cache(self, num_pages: int, page_size: int):
        """[L, P, ps, Hkv, D] K/V page pools (dense attention families only;
        page 0 is the reserved scratch page — runtime/kvpool.py)."""
        cfg = self.cfg
        if cfg.family not in ("dense",):
            raise ValueError(
                f"paged KV cache covers dense attention; {cfg.name} is "
                f"{cfg.family}")
        if cfg.sliding_window:
            raise ValueError(
                "paged KV cache keeps every position (pages, no ring wrap); "
                f"{cfg.name} uses a sliding window — serve it contiguous")
        L, dtype = cfg.num_layers, jnp.dtype(cfg.dtype)
        return {
            "k": jnp.zeros((L, num_pages, page_size, cfg.num_kv_heads,
                            cfg.head_dim), dtype),
            "v": jnp.zeros((L, num_pages, page_size, cfg.num_kv_heads,
                            cfg.head_dim), dtype),
        }

    def paged_step(self, params, cache, tokens, pos, block_table):
        """One paged pass: chunked prefill (S > 1) or paged decode (S == 1).

        tokens [B, S] int32; pos [B] per-sequence start positions;
        block_table [B, n] int32 page indices; ``cache`` is the
        ``init_paged_cache`` pool.  K/V rows for positions pos..pos+S-1 are
        written into their pages and the logical view is gathered back for
        attention, so the math is identical to the contiguous decode/prefill
        at the same positions (DESIGN.md §8).  Returns (last-position logits
        [B, v], new cache).

        Its parts carry stable ``jax.named_scope`` names in the ops'
        metadata, for a profiler's framework-op view: ``embed``, ``layers``
        (the scan; inside each layer ``attention`` with its ``page_write``
        and ``page_gather``, and ``mlp``), ``head``.
        """
        with jax.named_scope("embed"):
            x = self._embed(params, tokens)
        B, S = x.shape[:2]
        pos = jnp.asarray(pos, jnp.int32)
        positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        with jax.named_scope("layers"):
            x, aux, new_cache = self._scan_paged(params, x, positions, cache,
                                                 pos, block_table)
        with jax.named_scope("head"):
            logits = self._head(params, x[:, -1:, :])[:, 0]
        return logits, new_cache

    def decode_step(self, params, cache, tokens, pos):
        """One autoregressive step.  tokens [B] int32; ``pos`` is a scalar
        int32 (every sequence at the same depth — the fixed-batch serve path)
        or a [B] vector of per-sequence positions (continuous batching: each
        slot advances independently, with its own RoPE angle, cache slot and
        causal mask).

        Returns (logits [B, v], new_cache).
        """
        x = self._embed(params, tokens[:, None])
        B = x.shape[0]
        positions = layers_lib.decode_positions(pos, B)
        x, aux, new_cache = self._scan_decode(params, x, positions, cache, pos)
        return self._head(params, x)[:, 0], new_cache

    def decode_steps(self, params, cache, tokens, pos, num_tokens: int):
        """Fused greedy multi-token decode (see ``greedy_decode_loop``)."""
        return greedy_decode_loop(
            lambda c, tok, p: self.decode_step(params, c, tok, p),
            tokens, cache, pos, num_tokens)


@functools.lru_cache(maxsize=None)
def _cached_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


def get_model(cfg: ModelConfig) -> Model:
    return _cached_model(cfg)
