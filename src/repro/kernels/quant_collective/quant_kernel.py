"""Pallas TPU kernels for per-chunk symmetric collective quantization.

Row-blocked like the fused RMSNorm kernel: each grid cell handles a
[block_rows, K*chunk] tile entirely in VMEM.  All three ops are
bandwidth-bound elementwise passes, so the win is fusing the
reshape/scale/round/cast chain into one HBM read + one write.  The hidden
axis is pre-padded to a whole number of chunks on the host (zeros — inert
for abs-max and sliced off on the way out), so the in-kernel reshape to
(block_rows, K, chunk) is always exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _flatten_rows(x):
    h = x.shape[-1]
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    return x.reshape(rows, h), rows, h


def _pad_axes(x, block_rows: int, chunk: int):
    rows, hp = x.shape
    rpad = (-rows) % block_rows
    cpad = (-hp) % chunk
    if rpad or cpad:
        x = jnp.pad(x, ((0, rpad), (0, cpad)))
    return x


def _amax_kernel(x_ref, o_ref, *, chunk):
    x = x_ref[...].astype(jnp.float32)
    br, hp = x.shape
    o_ref[...] = jnp.abs(x).reshape(br, hp // chunk, chunk).max(axis=-1)


@functools.partial(jax.jit, static_argnames=("chunk", "block_rows", "interpret"))
def chunk_amax_pallas(x, chunk: int = 128, block_rows: int = 256,
                      interpret: bool = False):
    xf, rows, h = _flatten_rows(x)
    k = -(-h // chunk)
    block_rows = min(block_rows, rows)
    xf = _pad_axes(xf, block_rows, chunk)
    n = xf.shape[0] // block_rows
    out = pl.pallas_call(
        functools.partial(_amax_kernel, chunk=chunk),
        grid=(n,),
        in_specs=[pl.BlockSpec((block_rows, k * chunk), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xf.shape[0], k), jnp.float32),
        interpret=interpret,
    )(xf)
    return out[:rows].reshape(*x.shape[:-1], k)


def _quantize_kernel(x_ref, s_ref, o_ref, *, chunk, clip_lo, clip_hi,
                     integer):
    x = x_ref[...].astype(jnp.float32)
    br, hp = x.shape
    xc = x.reshape(br, hp // chunk, chunk) / s_ref[...][..., None]
    if integer:
        xc = jnp.round(xc)
    xc = jnp.clip(xc, clip_lo, clip_hi)
    o_ref[...] = xc.reshape(br, hp).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "qdtype", "block_rows", "interpret"))
def chunk_quantize_pallas(x, scales, chunk: int = 128, qdtype=jnp.int8,
                          block_rows: int = 256, interpret: bool = False):
    xf, rows, h = _flatten_rows(x)
    k = -(-h // chunk)
    sf = scales.reshape(rows, k)
    block_rows = min(block_rows, rows)
    xf = _pad_axes(xf, block_rows, chunk)
    sf = _pad_axes(sf, block_rows, 1)
    sf = jnp.where(sf == 0.0, 1.0, sf)  # padded rows: avoid 0/0 in-kernel
    integer = jnp.issubdtype(qdtype, jnp.integer)
    if integer:
        info = jnp.iinfo(qdtype)
        clip_lo, clip_hi = float(info.min + 1), float(info.max)
    else:
        fmax = float(jnp.finfo(qdtype).max)  # saturate, don't overflow to nan
        clip_lo, clip_hi = -fmax, fmax
    n = xf.shape[0] // block_rows
    out = pl.pallas_call(
        functools.partial(_quantize_kernel, chunk=chunk,
                          clip_lo=clip_lo, clip_hi=clip_hi, integer=integer),
        grid=(n,),
        in_specs=[pl.BlockSpec((block_rows, k * chunk), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, k * chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, qdtype),
        interpret=interpret,
    )(xf, sf)
    return out[:rows, :h].reshape(x.shape)


def _pack_kernel(q_ref, o_ref):
    # half-split pairing (element i with i + h/2): two contiguous slices,
    # no lane-splitting reshape, which Mosaic cannot lower
    m = o_ref.shape[-1]
    lo = q_ref[:, :m].astype(jnp.int32) & 0xF
    hi = q_ref[:, m:].astype(jnp.int32) & 0xF
    o_ref[...] = (lo | (hi << 4)).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def nibble_pack_pallas(q, block_rows: int = 256, interpret: bool = False):
    qf, rows, h = _flatten_rows(q)
    if h % 2:
        raise ValueError(f"nibble packing needs an even last axis, got {h}")
    block_rows = min(block_rows, rows)
    qf = _pad_axes(qf, block_rows, 2)
    n = qf.shape[0] // block_rows
    out = pl.pallas_call(
        _pack_kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((block_rows, h), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, h // 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((qf.shape[0], h // 2), jnp.uint8),
        interpret=interpret,
    )(qf)
    return out[:rows].reshape(*q.shape[:-1], h // 2)


def _unpack_kernel(b_ref, o_ref):
    m = b_ref.shape[-1]
    b = b_ref[...].astype(jnp.int32)
    o_ref[:, :m] = (((b & 0xF) ^ 8) - 8).astype(jnp.int8)
    o_ref[:, m:] = ((((b >> 4) & 0xF) ^ 8) - 8).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def nibble_unpack_pallas(b, block_rows: int = 256, interpret: bool = False):
    bf, rows, m = _flatten_rows(b)
    block_rows = min(block_rows, rows)
    bf = _pad_axes(bf, block_rows, 1)
    n = bf.shape[0] // block_rows
    out = pl.pallas_call(
        _unpack_kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((block_rows, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, 2 * m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bf.shape[0], 2 * m), jnp.int8),
        interpret=interpret,
    )(bf)
    return out[:rows].reshape(*b.shape[:-1], 2 * m)


def _dequantize_kernel(q_ref, s_ref, o_ref, *, chunk):
    q = q_ref[...].astype(jnp.float32)
    br, hp = q.shape
    xc = q.reshape(br, hp // chunk, chunk) * s_ref[...][..., None]
    o_ref[...] = xc.reshape(br, hp).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "out_dtype", "block_rows",
                                    "interpret"))
def chunk_dequantize_pallas(q, scales, chunk: int = 128,
                            out_dtype=jnp.float32, block_rows: int = 256,
                            interpret: bool = False):
    qf, rows, h = _flatten_rows(q)
    k = -(-h // chunk)
    sf = scales.reshape(rows, k)
    block_rows = min(block_rows, rows)
    qf = _pad_axes(qf, block_rows, chunk)
    sf = _pad_axes(sf, block_rows, 1)
    n = qf.shape[0] // block_rows
    out = pl.pallas_call(
        functools.partial(_dequantize_kernel, chunk=chunk),
        grid=(n,),
        in_specs=[pl.BlockSpec((block_rows, k * chunk), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, k * chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, out_dtype),
        interpret=interpret,
    )(qf, sf)
    return out[:rows, :h].reshape(q.shape)
