"""Compile-only checks for a TPU v5e:2x2 that is described, not attached.

The TPU compiler installed beside JAX compiles for a topology it is only
told about, so these tests catch what the chip's compiler would refuse
(Mosaic kernel layouts, programs that do not fit 16 GB) without a chip.
Nothing runs: no result or time comes from here.  InternLM2-1.8B is the
chip smoke's model (``chip_smoke.py``), at its published widths and full
depth.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.core import parallel_exec as px
from repro.kernels.quant_collective import quant_kernel as qk
from repro.models.transformer import get_model

HBM_BYTES = 16e9          # one v5e chip
NUM_PAGES, PAGE_SIZE, SLOTS, MAX_LEN = 1025, 16, 8, 2048
# the benchmark cell's pool: 24 slots of 2048 positions in pages of 16
CELL_PAGES, CELL_SLOTS = 3073, 24


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    return get_config("internlm2-1.8b")


def _shapes(tree, sharding):
    """ShapeDtypeStructs of a shape pytree, placed by ``sharding`` (one
    sharding for every leaf, or a matching pytree of them)."""
    if not isinstance(sharding, (dict, list, tuple)):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), tree, sharding)


def _step_args(batch, q_len, sharding):
    int_arg = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                 sharding=sharding)
    return (int_arg((batch, q_len)), int_arg((batch,)),
            int_arg((batch, MAX_LEN // PAGE_SIZE)))


def _pool_shaped_moves(hlo: str, pages: int, kv_heads: int, head_dim: int):
    """Lines of a compiled module whose ``copy``, ``dynamic-slice`` or
    ``dynamic-update-slice`` yields a whole pool or a whole layer of one:
    a result shape ending in [pages, page_size, kv_heads, head_dim]."""
    shape = rf"\[(\d+,)*{pages},{PAGE_SIZE},{kv_heads},{head_dim}\]"
    op = r"\b(copy|dynamic-slice|dynamic-update-slice)\("
    return [line for line in hlo.splitlines()
            if re.search(rf"= \w+{shape}", line) and re.search(op, line)]


@pytest.mark.parametrize("batch,q_len", [(SLOTS, 1), (1, 256)],
                         ids=["decode", "prefill_chunk"])
def test_gspmd_paged_step_fits_one_chip(cfg, one_chip, batch, q_len):
    """The one-chip serving step at published widths: the paged pass for
    the decode batch and for one 256-token prefill chunk."""
    model = get_model(cfg)
    params = _shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    cache = _shapes(jax.eval_shape(
        lambda: model.init_paged_cache(NUM_PAGES, PAGE_SIZE)), one_chip)
    compiled = jax.jit(model.paged_step, donate_argnums=(1,)).lower(
        params, cache, *_step_args(batch, q_len, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert 0 < mem.argument_size_in_bytes < HBM_BYTES
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_tp4_paged_step_compiles_on_four_chips(cfg, topo,
                                               no_persistent_cache):
    """The explicit TP engine's paged decode step on the 2x2 mesh: vocab,
    heads and MLP split four ways, the page pools split on kv heads."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("tp",),
                axis_types=(AxisType.Auto,))
    shard = lambda spec: NamedSharding(mesh, spec)
    model = get_model(cfg)
    params = _shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     jax.tree.map(shard, px.tp_param_specs(cfg),
                                  is_leaf=lambda x: isinstance(x, P)))
    cache = _shapes(jax.eval_shape(
        lambda: model.init_paged_cache(NUM_PAGES, PAGE_SIZE)),
        shard(P(None, None, None, "tp", None)))
    step = px.tp_paged_step(cfg, mesh)
    compiled = step.lower(params, cache,
                          *_step_args(SLOTS, 1, shard(P()))).compile()
    mem = compiled.memory_analysis()
    # per-device bytes: a quarter of the model and of the pool, plus the
    # replicated norms
    assert 0 < mem.argument_size_in_bytes < HBM_BYTES / 4
    assert "all-reduce" in compiled.as_text()


@pytest.mark.parametrize("engine", ["gspmd", "tp4"])
@pytest.mark.parametrize("batch,q_len", [(CELL_SLOTS, 1), (1, 256)],
                         ids=["decode", "prefill_chunk"])
def test_paged_step_updates_pool_in_place(cfg, topo, no_persistent_cache,
                                          engine, batch, q_len):
    """At the benchmark cell's pool, the paged step with its pool donated
    updates the pool in place: no temporary near the pool's size, and no
    copy, slice or stacking of a whole pool (or a whole layer of it) in
    the compiled module.  The tp4 step is checked per device."""
    model = get_model(cfg)
    if engine == "gspmd":
        one = SingleDeviceSharding(topo.devices[0])
        params = _shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                         one)
        pool_sharding, arg_sharding, kv_heads = one, one, cfg.num_kv_heads
        step = jax.jit(model.paged_step, donate_argnums=(1,))
    else:
        mesh = Mesh(np.asarray(topo.devices).reshape(4), ("tp",),
                    axis_types=(AxisType.Auto,))
        shard = lambda spec: NamedSharding(mesh, spec)
        params = _shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                         jax.tree.map(shard, px.tp_param_specs(cfg),
                                      is_leaf=lambda x: isinstance(x, P)))
        pool_sharding = shard(P(None, None, None, "tp", None))
        arg_sharding, kv_heads = shard(P()), cfg.num_kv_heads // 4
        step = px.tp_paged_step(cfg, mesh)
    cache = _shapes(jax.eval_shape(
        lambda: model.init_paged_cache(CELL_PAGES, PAGE_SIZE)), pool_sharding)
    compiled = step.lower(params, cache,
                          *_step_args(batch, q_len, arg_sharding)).compile()
    pool_bytes = 2 * (cfg.num_layers * CELL_PAGES * PAGE_SIZE * kv_heads
                      * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05 * pool_bytes
    assert _pool_shaped_moves(compiled.as_text(), CELL_PAGES, kv_heads,
                              cfg.head_dim) == []


@pytest.mark.parametrize("kernel", ["chunk_amax", "chunk_quantize",
                                    "chunk_dequantize", "nibble_pack",
                                    "nibble_unpack"])
def test_quant_collective_kernel_compiles_at_decode_shape(cfg, one_chip,
                                                          kernel):
    """Each quantized-collective Pallas kernel lowers through Mosaic at the
    decode all-reduce's [8, d_model] shape."""
    rows, h = SLOTS, cfg.d_model
    k = h // 128
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    x, scales = arg((rows, h), jnp.bfloat16), arg((rows, k), jnp.float32)
    q = arg((rows, h), jnp.int8)
    fn, args = {
        "chunk_amax": (qk.chunk_amax_pallas, (x,)),
        "chunk_quantize": (qk.chunk_quantize_pallas, (x, scales)),
        "chunk_dequantize": (qk.chunk_dequantize_pallas, (q, scales)),
        "nibble_pack": (qk.nibble_pack_pallas, (q,)),
        "nibble_unpack": (qk.nibble_unpack_pallas,
                          (arg((rows, h // 2), jnp.uint8),)),
    }[kernel]
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
