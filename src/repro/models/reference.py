"""Plain float32 reference forward of the dense GQA decoder.

Written straight from the layer equations in ``jax.numpy``, independent of
``models/`` (no cache, no pages, no kernels, no batching, no sharding), so
that the serving path can be checked against it on logits.  It follows the
repo's parameter conventions, which differ from a published checkpoint's in
one place: RMSNorm scales by ``1 + w`` (weights stored as offsets from 1).
Everything else is the published InternLM2 / Llama block: pre-norm
attention with half-split RoPE, grouped-query heads, a causal mask, then a
pre-norm SwiGLU MLP; untied output head.

Every matmul runs at ``highest`` precision: on a TPU a float32 matmul runs
in lower precision otherwise.  Weights stay in their stored dtype and each
layer's slice is cast to float32 inside the layer scan, so no float32 copy
of the whole model is ever held.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None, None].astype(jnp.float32) * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


@functools.partial(jax.jit, static_argnums=0)
def _last_logits(cfg: ModelConfig, params, tokens):
    S = tokens.shape[0]
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]                       # [S, T]
    x = params["embed"][tokens].astype(jnp.float32)            # [S, h]

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        xn = _rms(x, p["ln1"], cfg.norm_eps)
        q = _rope((xn @ p["wq"]).reshape(S, H, D), pos, cfg.rope_theta)
        k = _rope((xn @ p["wk"]).reshape(S, Hkv, D), pos, cfg.rope_theta)
        v = (xn @ p["wv"]).reshape(S, Hkv, D)
        k = jnp.repeat(k, H // Hkv, axis=1)    # query head i reads kv i // G
        v = jnp.repeat(v, H // Hkv, axis=1)
        s = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(D)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hst,thd->shd", a, v).reshape(S, H * D)
        x = x + o @ p["wo"]
        xn = _rms(x, p["ln2"], cfg.norm_eps)
        return x + (jax.nn.silu(xn @ p["w1"]) * (xn @ p["w3"])) @ p["w2"], None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    xn = _rms(x[-1], params["final_norm"].astype(jnp.float32), cfg.norm_eps)
    return (xn @ params["lm_head"].astype(jnp.float32))[:cfg.vocab_size]


def reference_last_logits(cfg: ModelConfig, params, tokens) -> np.ndarray:
    """float32 logits [vocab_size] at the last position of the 1-D prompt
    ``tokens``, from a ``Model.init`` pytree of a dense SwiGLU config."""
    if (cfg.family != "dense" or cfg.activation != "swiglu"
            or cfg.tie_embeddings or cfg.sliding_window
            or cfg.scale_embedding):
        raise ValueError(f"the reference covers untied dense SwiGLU "
                         f"decoders with full attention; not {cfg.name}")
    with jax.default_matmul_precision("highest"):
        out = _last_logits(cfg, params,
                           jnp.asarray(np.asarray(tokens, np.int32)))
    return np.asarray(out)
