"""Dense decoder: grouped-query attention and a gated (SwiGLU) MLP.

Everything the benchmark knows of this block, read from a configuration
file of ``bench/configs`` (published key names):

- the program's ``ModelConfig`` fields the file fixes, and the check of
  the program against them;
- the weight layout the program's dense decoder reads (stacked layers on
  a leading axis, norms stored as offsets from 1, vocabulary padded to a
  multiple of 128);
- operations and least bytes of one paged pass, from shapes alone.  The
  counts are what the algorithm needs, not what the program happens to
  move:

  - FLOPs count each multiply-add as 2: the projections and the MLP per
    new token, attention scores and values against every earlier position
    and the token itself, and the output head once per sequence (a paged
    pass returns the last position's logits only).
  - Least bytes of a decode step, per chip of a tensor-parallel group of
    ``t``: the chip's share of the weights once (the embedding only for
    the rows looked up), the live keys and values of every sequence read
    once, and the new token's keys and values written once.
"""
from __future__ import annotations

from typing import Iterable

import numpy as np

from bench.weights import dtype_bytes, padded_vocab


def _d(cfg: dict):
    h = int(cfg["hidden_size"])
    H = int(cfg["num_attention_heads"])
    D = int(cfg.get("head_dim") or h // H)
    return (h, int(cfg["num_hidden_layers"]), H,
            int(cfg["num_key_value_heads"]), D, int(cfg["intermediate_size"]),
            int(cfg["vocab_size"]))


# ---------------------------------------------------------------- program
def program_fields(cfg: dict) -> dict:
    """The program's ``ModelConfig`` fields this file fixes."""
    return {"family": "dense", "activation": "swiglu",
            "d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "num_layers": cfg["num_hidden_layers"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "vocab_size": cfg["vocab_size"],
            "rope_theta": cfg["rope_theta"], "norm_eps": cfg["rms_norm_eps"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "dtype": cfg["dtype"]}


def check_program(cfg: dict, pcfg) -> None:
    """Raises ``ValueError`` naming each field where the program's config
    differs from the file."""
    bad = {k: (getattr(pcfg, k), v) for k, v in program_fields(cfg).items()
           if getattr(pcfg, k) != v}
    if bad:
        raise ValueError("; ".join(f"{k}: program {g!r}, file {w!r}"
                                   for k, (g, w) in bad.items()))


# ---------------------------------------------------------------- weights
def leaf_specs(cfg: dict) -> dict:
    """{path: (shape, std)} of every leaf; std 0 means zeros."""
    h, L, H, Hkv, D, ff, _ = _d(cfg)
    pv = padded_vocab(cfg)
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


# ---------------------------------------------------------------- counts
def layer_matmul_params(cfg: dict) -> int:
    h, _, H, Hkv, D, ff, _ = _d(cfg)
    return h * (H * D + 2 * Hkv * D) + H * D * h + 3 * h * ff


def param_count(cfg: dict) -> int:
    """Every weight: layers (projections, MLP, two norms), embedding,
    untied output head and final norm."""
    h, L, *_, V = _d(cfg)
    head = 0 if cfg.get("tie_word_embeddings") else V * h
    return L * (layer_matmul_params(cfg) + 2 * h) + V * h + head + h


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one position over all layers."""
    _, L, _, Hkv, D, _, _ = _d(cfg)
    return 2 * L * Hkv * D * dtype_bytes(cfg)


def pass_flops(cfg: dict, q_len: int, start: int) -> float:
    """One sequence's pass over ``q_len`` new tokens at positions
    start..start+q_len-1, with the head at the last position."""
    h, L, H, _, D, _, V = _d(cfg)
    mm = 2.0 * L * layer_matmul_params(cfg) * q_len
    # query at position p reads p + 1 keys: scores and values, 2 flops each
    ctx = q_len * start + q_len * (q_len + 1) / 2.0
    attn = 4.0 * L * H * D * ctx
    return mm + attn + 2.0 * h * V


def decode_flops(cfg: dict, positions: Iterable[int]) -> float:
    """A decode step over live sequences whose new tokens sit at
    ``positions``."""
    return sum(pass_flops(cfg, 1, int(p)) for p in positions)


def decode_least_bytes(cfg: dict, positions: Iterable[int],
                       t: int = 1) -> float:
    """Least bytes one chip of ``t`` moves in a decode step over live
    sequences whose new tokens sit at ``positions``."""
    h, L, *_, V = _d(cfg)
    b = dtype_bytes(cfg)
    positions = [int(p) for p in positions]
    head = 0 if cfg.get("tie_word_embeddings") else V * h
    weights = (L * layer_matmul_params(cfg) + head) * b / t \
        + (2 * L * h + h) * b
    embed_rows = len(positions) * h * b / t
    kv = kv_bytes_per_token(cfg) / t
    return weights + embed_rows + kv * (sum(positions) + len(positions))
