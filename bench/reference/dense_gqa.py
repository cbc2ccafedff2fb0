"""Plain float32 reference of a dense decoder with grouped-query attention.

Written from the layer equations of the published InternLM2 / Llama
block, in ``jax.numpy``, and independent of the program under test: it
reads the configuration file's numbers and the benchmark's weights (the
layout of ``bench/weights.py``) and nothing else.  Pre-norm attention with
half-split rotary embeddings and a causal mask, then a pre-norm gated
SiLU MLP; RMSNorm gains are stored as offsets from 1.

One call scores a whole sequence: the prompt followed by the tokens the
program served, padded at the end to a fixed length (positions after the
sequence cannot change the ones before it under the causal mask, and one
length means one compiled program).  Layers run one at a time in a scan,
each cast to float32 on its own, so no float32 copy of the model is ever
held; every matmul runs at ``highest`` precision.

``control=True`` adds the control: the same pass with every projection's
operands rounded to float8 (e4m3, one scale per tensor), the step below
the configuration's bfloat16.  For each position it reports the float32
logit of the token that the float8 pass ranks first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0           # largest finite float8_e4m3fn


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _f8(a):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _forward(cfg: dict, params, tokens, low: bool):
    """float32 logits [S, padded vocab] of every position."""
    S = tokens.shape[0]
    h = int(cfg["hidden_size"])
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    D = int(cfg.get("head_dim") or h // H)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    q = _f8 if low else (lambda a: a)
    mm = lambda a, b: _mm(q(a), q(b))
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        xn = _rms(x, p["ln1"], eps)
        qh = _rope(mm(xn, p["wq"]).reshape(S, H, D), pos, theta)
        kh = _rope(mm(xn, p["wk"]).reshape(S, Hkv, D), pos, theta)
        vh = mm(xn, p["wv"]).reshape(S, Hkv, D)
        kh = jnp.repeat(kh, H // Hkv, axis=1)   # query head i reads kv i//G
        vh = jnp.repeat(vh, H // Hkv, axis=1)
        s = jnp.einsum("shd,thd->hst", qh, kh, precision=HIGHEST) \
            / np.sqrt(D)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hst,thd->shd", a, vh,
                       precision=HIGHEST).reshape(S, H * D)
        x = x + mm(o, p["wo"])
        xn = _rms(x, p["ln2"], eps)
        return x + mm(jax.nn.silu(mm(xn, p["w1"])) * mm(xn, p["w3"]),
                      p["w2"]), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    xn = _rms(x, params["final_norm"].astype(jnp.float32), eps)
    head = params["embed"].T if cfg.get("tie_word_embeddings") \
        else params["lm_head"]
    return mm(xn, head.astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _scorer(cfg_items: tuple, control: bool):
    cfg = dict(cfg_items)
    V = int(cfg["vocab_size"])

    def fn(params, tokens, targets):
        logits = _forward(cfg, params, tokens, low=False)[:, :V]
        out = {"best": jnp.max(logits, -1),
               "served": jnp.take_along_axis(logits, targets[:, None],
                                             -1)[:, 0]}
        if control:
            low = _forward(cfg, params, tokens, low=True)[:, :V]
            pick = jnp.argmax(low, -1)
            out["control"] = jnp.take_along_axis(logits, pick[:, None],
                                                 -1)[:, 0]
        return out

    return jax.jit(fn)


def score(cfg: dict, params, tokens, targets, control: bool = False) -> dict:
    """Per position of the padded sequence ``tokens`` [S]: the float32
    reference's best logit (``best``), its logit of ``targets`` [S]
    (``served``), and with ``control`` its logit of the float8 pass's first
    choice (``control``); numpy float32 arrays [S]."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    out = _scorer(items, control)(params, np.asarray(tokens, np.int32),
                                  np.asarray(targets, np.int32))
    return {k: np.asarray(v, np.float32) for k, v in out.items()}
