"""Pure-jnp oracle for per-chunk symmetric collective quantization.

The quantized two-step all-reduce (DESIGN.md §12) needs three dense ops on
the activation row entering a TP ``psum``:

* ``chunk_amax_ref``    — abs-max over each ``chunk``-wide block of the last
  axis (the per-chunk scale statistic, exchanged via ``pmax``),
* ``chunk_quantize_ref`` — symmetric round-to-nearest onto the quant grid,
* ``chunk_dequantize_ref`` — back to the accumulation dtype.

The hidden axis is padded up to a whole number of chunks and sliced back, so
``h % chunk != 0`` (odd remainders) is exact: the zero padding can neither
raise an abs-max nor leak into the sliced output.
"""
from __future__ import annotations

import jax.numpy as jnp


def _pad_to_chunks(x, chunk: int):
    h = x.shape[-1]
    k = -(-h // chunk)
    pad = k * chunk - h
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, k


def chunk_amax_ref(x, chunk: int):
    """Per-chunk abs-max of the last axis: [..., h] -> [..., K] float32."""
    xp, k = _pad_to_chunks(jnp.abs(x.astype(jnp.float32)), chunk)
    return xp.reshape(*x.shape[:-1], k, chunk).max(axis=-1)


def chunk_quantize_ref(x, scales, chunk: int, qdtype):
    """Symmetric quantize: q = round(x / scale) per chunk, cast to qdtype.

    ``scales`` is [..., K] float32 (broadcast over each chunk).  Integer
    targets are clipped to the signed range as a guard; callers are expected
    to have built ``scales`` with enough headroom (see
    ``ops.collective_qmax``) that the clip never actually binds.
    """
    h = x.shape[-1]
    xp, k = _pad_to_chunks(x.astype(jnp.float32), chunk)
    xc = xp.reshape(*x.shape[:-1], k, chunk) / scales[..., None]
    if jnp.issubdtype(qdtype, jnp.integer):
        info = jnp.iinfo(qdtype)
        xc = jnp.clip(jnp.round(xc), info.min + 1, info.max)
    else:
        fmax = float(jnp.finfo(qdtype).max)  # saturate, don't overflow to nan
        xc = jnp.clip(xc, -fmax, fmax)
    return xc.reshape(*x.shape[:-1], k * chunk)[..., :h].astype(qdtype)


def chunk_dequantize_ref(q, scales, chunk: int, out_dtype):
    """Dequantize: x = q * scale per chunk, cast to ``out_dtype``."""
    h = q.shape[-1]
    qp, k = _pad_to_chunks(q.astype(jnp.float32), chunk)
    xc = qp.reshape(*q.shape[:-1], k, chunk) * scales[..., None]
    return xc.reshape(*q.shape[:-1], k * chunk)[..., :h].astype(out_dtype)


def nibble_pack_ref(q):
    """Pack int4 values (int8 storage, |q| <= 7) two-per-byte: [..., h] ->
    [..., h//2] uint8.  Byte i holds element i in its low nibble and
    element i + h/2 in its high nibble (half-split pairing) —
    two's-complement truncation to 4 bits, inverted exactly by
    ``nibble_unpack_ref``."""
    h = q.shape[-1]
    if h % 2:
        raise ValueError(f"nibble packing needs an even last axis, got {h}")
    u = q.astype(jnp.uint8)
    return (u[..., :h // 2] & 0xF) | ((u[..., h // 2:] & 0xF) << 4)


def nibble_unpack_ref(b):
    """Unpack two-per-byte nibbles back to int8: [..., m] -> [..., 2m],
    low nibbles first, then high nibbles, sign-extending each 4-bit field
    ((n ^ 8) - 8)."""
    lo = (b & 0xF).astype(jnp.int8)
    hi = ((b >> 4) & 0xF).astype(jnp.int8)
    return jnp.concatenate([(lo ^ 8) - 8, (hi ^ 8) - 8], axis=-1)
