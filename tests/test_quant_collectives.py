"""Quantized collectives (DESIGN.md §12): kernels, the two-step psum, and
the predicted == compiled invariant under quantization.

Layers under test, bottom up:
  * kernels/quant_collective — per-chunk amax/quantize/dequantize: jnp ref
    vs Pallas-interpret bitwise, odd chunk remainders, the zero-chunk scale
    guard, and the summation-headroom qmax table;
  * core/parallel_exec.quantized_psum — exact agreement with a numpy
    simulation of the shared-scale two-step (the int8 reduce-scatter sum is
    EXACT by the qmax headroom), bounded drift vs the full-width psum, and
    bitwise identity + zero quant ops at t=1;
  * predicted == compiled: ``comm_ops_for(quant=...)`` must match the
    decode-step HLO in counts AND wire bytes for TP layouts in both unroll
    modes, and ``hybrid_stage_collectives(quant=...)`` must match every
    stage of the quantized hybrid engine;
  * runtime/backends + slo/planner: decomposed decode rows, the
    paged/gspmd rejections, strictly-lower predicted volume, and the
    volume-budget frontier re-entry the planner docstring promises.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import commodel as cm
from repro.core import parallel_exec as px
from repro.core.hlo_comm import parse_hlo_collectives, summarize
from repro.kernels.quant_collective import (QUANT_DTYPES, QUANT_TOLERANCE,
                                            chunk_amax, chunk_dequantize,
                                            chunk_quantize, collective_qmax,
                                            nibble_pack, nibble_unpack,
                                            scales_from_amax)
from repro.kernels.quant_collective.ref import (chunk_amax_ref,
                                                chunk_dequantize_ref,
                                                chunk_quantize_ref,
                                                nibble_pack_ref,
                                                nibble_unpack_ref)
from repro.models.transformer import get_model

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 host-platform devices")
needs_pair = pytest.mark.skipif(len(jax.devices()) < 2,
                                reason="needs 2 host-platform devices")


# ---------------------------------------------------------------------------
# kernel package: ref vs Pallas-interpret, remainders, guards
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,chunk", [((4, 3072), 128), ((3, 100), 32),
                                         ((2, 5, 257), 128)])
@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_roundtrip_error_bounded_per_chunk(shape, chunk, quant):
    """|x − dequant(quantize(x))| ≤ scale/2 (int8) / one e4m3 mantissa step
    (fp8), per chunk — including ragged tails where h % chunk != 0."""
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32) * 3.0
    qmax = collective_qmax(quant, 1)
    scales = scales_from_amax(chunk_amax(x, chunk), qmax)
    q = chunk_quantize(x, scales, chunk, quant)
    assert q.dtype == QUANT_DTYPES[quant]
    back = chunk_dequantize(q, scales, chunk, jnp.float32)
    assert back.shape == x.shape
    K = cm.quant_chunks(shape[-1], chunk)
    err = np.abs(np.asarray(x) - np.asarray(back))
    s = np.asarray(scales)
    for k in range(K):
        sl = err[..., k * chunk:(k + 1) * chunk]
        bound = s[..., k] * (0.5 if quant == "int8" else 2.0 ** -3 * qmax)
        assert (sl <= bound[..., None] + 1e-6).all()


@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_interpret_kernels_match_ref_bitwise(monkeypatch, quant):
    """The Pallas kernels (interpret mode on CPU) and the jnp oracle are
    the same function, bit for bit, for every entry point."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 257), jnp.float32)
    chunk = 64
    amax_p = chunk_amax(x, chunk)
    np.testing.assert_array_equal(np.asarray(amax_p),
                                  np.asarray(chunk_amax_ref(x, chunk)))
    scales = scales_from_amax(amax_p, collective_qmax(quant, 2))
    q_p = chunk_quantize(x, scales, chunk, quant)
    q_r = chunk_quantize_ref(x, scales, chunk, QUANT_DTYPES[quant])
    np.testing.assert_array_equal(np.asarray(q_p).view(np.uint8),
                                  np.asarray(q_r).view(np.uint8))
    np.testing.assert_array_equal(
        np.asarray(chunk_dequantize(q_p, scales, chunk, jnp.float32)),
        np.asarray(chunk_dequantize_ref(q_r, scales, chunk, jnp.float32)))


def test_zero_chunk_guard():
    """An all-zero chunk quantizes through scale 1.0 and round-trips to
    exact zeros — no 0/0 anywhere."""
    x = jnp.zeros((2, 256), jnp.float32).at[:, 128:].set(1.5)
    scales = scales_from_amax(chunk_amax(x, 128), collective_qmax("int8", 2))
    assert np.asarray(scales)[0, 0] == 1.0
    back = chunk_dequantize(chunk_quantize(x, scales, 128, "int8"),
                            scales, 128, jnp.float32)
    assert np.isfinite(np.asarray(back)).all()
    np.testing.assert_array_equal(np.asarray(back)[:, :128], 0.0)


def test_nibble_pack_unpack_roundtrip_every_value():
    """Every int4 value pair survives pack -> unpack bitwise, in every
    lane position, and the packed form is half the bytes."""
    vals = np.arange(-8, 8, dtype=np.int8)           # full 4-bit range
    # byte i pairs elements i and i + 16: row r pairs every value with vals[r]
    q = jnp.asarray(np.concatenate(np.meshgrid(vals, vals), -1))
    packed = nibble_pack(q)
    assert packed.dtype == jnp.uint8
    assert packed.shape == (16, 16)
    np.testing.assert_array_equal(np.asarray(nibble_unpack(packed)),
                                  np.asarray(q))
    with pytest.raises(ValueError):
        nibble_pack(jnp.zeros((2, 3), jnp.int8))     # odd last axis


def test_nibble_kernels_match_ref_bitwise(monkeypatch):
    """Pallas pack/unpack (interpret mode) == the jnp oracle, bit for bit,
    including ragged row counts that exercise the row padding."""
    q = jnp.asarray(np.random.default_rng(0).integers(
        -7, 8, size=(5, 38), dtype=np.int8))
    want_packed = np.asarray(nibble_pack_ref(q))
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    got_packed = np.asarray(nibble_pack(q))
    np.testing.assert_array_equal(got_packed, want_packed)
    np.testing.assert_array_equal(
        np.asarray(nibble_unpack(jnp.asarray(got_packed))),
        np.asarray(nibble_unpack_ref(jnp.asarray(want_packed))))


def test_collective_qmax_headroom_table():
    """qmax · t never exceeds the wire dtype's range — the property that
    makes the int8 reduce-scatter sum exact and the fp8 one unsaturated."""
    for t in (1, 2, 4, 8):
        assert collective_qmax("int8", t) * t <= 127
        assert collective_qmax("fp8", t) * t <= 448.0
        # int4 keeps the full grid at every t: headroom comes from the
        # packed path's exact int32 accumulation, not the qmax table
        assert collective_qmax("int4", t) == 7.0
    assert collective_qmax("int8", 4) == 31.0
    assert collective_qmax("fp8", 4) == 112.0
    with pytest.raises(ValueError):
        collective_qmax("int2", 2)
    with pytest.raises(ValueError):
        collective_qmax("int8", 0)


def test_quant_tolerance_contract_shape():
    """The numerics contract is explicit and single-homed: both wire modes
    carry a match floor and a drift ceiling, and fp8 (3 mantissa bits) is
    never promised tighter than int8."""
    assert set(QUANT_TOLERANCE) == set(QUANT_DTYPES) == \
        {"int8", "fp8", "int4"}
    for mode, tol in QUANT_TOLERANCE.items():
        assert set(tol) == {"token_match_floor", "logit_drift_ceiling"}
        assert 0.0 < tol["token_match_floor"] <= 1.0
        assert tol["logit_drift_ceiling"] > 0.0
    assert QUANT_TOLERANCE["fp8"]["token_match_floor"] <= \
        QUANT_TOLERANCE["int8"]["token_match_floor"]
    assert QUANT_TOLERANCE["fp8"]["logit_drift_ceiling"] >= \
        QUANT_TOLERANCE["int8"]["logit_drift_ceiling"]
    # a 4-bit grid is never promised tighter than the 8-bit one
    assert QUANT_TOLERANCE["int4"]["token_match_floor"] <= \
        QUANT_TOLERANCE["fp8"]["token_match_floor"]
    assert QUANT_TOLERANCE["int4"]["logit_drift_ceiling"] >= \
        QUANT_TOLERANCE["fp8"]["logit_drift_ceiling"]


# ---------------------------------------------------------------------------
# quantized_psum: exact numpy simulation + drift bound + t=1 identity
# ---------------------------------------------------------------------------


def _run_quantized_psum(x_ranks, t, quant, chunk):
    """shard_map quantized_psum over the first axis of [t, rows, h]."""
    mesh = px.make_tp_mesh(t)
    fn = jax.jit(jax.shard_map(
        lambda xs: px.quantized_psum(xs, "tp", t, quant=quant, chunk=chunk),
        mesh=mesh, in_specs=P("tp"), out_specs=P("tp"), check_vma=False))
    out = np.asarray(fn(x_ranks))
    # every rank must hold the identical dequantized sum
    for r in range(1, t):
        np.testing.assert_array_equal(out[r], out[0])
    return out[0]


def _sim_scales(x_ranks, t, quant, chunk):
    """Shared per-chunk scales from the globally pmax'ed abs-max."""
    x = np.asarray(x_ranks, np.float32)          # [t, rows, h]
    h = x.shape[-1]
    K = cm.quant_chunks(h, chunk)
    pad = np.zeros(x.shape[:-1] + (K * chunk - h,), np.float32)
    xp = np.concatenate([x, pad], -1).reshape(x.shape[:-1] + (K, chunk))
    amax = np.abs(xp).max(-1).max(0)             # global (pmax) per chunk
    qmax = collective_qmax(quant, t)
    return np.where(amax > 0, amax / qmax, 1.0).astype(np.float32)


def _simulate(x_ranks, t, quant, chunk):
    """Numpy oracle of the shared-scale two-step."""
    x = np.asarray(x_ranks, np.float32)          # [t, rows, h]
    h = x.shape[-1]
    K = cm.quant_chunks(h, chunk)
    pad = np.zeros(x.shape[:-1] + (K * chunk - h,), np.float32)
    xp = np.concatenate([x, pad], -1).reshape(x.shape[:-1] + (K, chunk))
    scales = _sim_scales(x_ranks, t, quant, chunk)
    if quant == "int8":
        q = np.clip(np.rint(xp / scales[None, ..., None]), -127, 127)
        total = q.sum(0)                         # exact: |sum| ≤ t·qmax ≤ 127
    elif quant == "int4":
        q = np.rint(xp / scales[None, ..., None])    # |q| ≤ 7 by the scales
        r = q.sum(0)                             # exact int32 block sum
        total = np.clip(np.rint(r / t), -7, 7) * t   # requantize-by-t
    else:
        q = (xp / scales[None, ..., None]).astype(jnp.float8_e4m3fn)
        total = q[0].astype(np.float32)
        for r in range(1, t):                    # fp8 ring adds in f32 here
            total = total + q[r].astype(np.float32)
    out = (total * scales[..., None]).reshape(x.shape[1:-1] + (K * chunk,))
    return out[..., :h].astype(np.float32)


@needs_pair
@pytest.mark.parametrize("h,chunk", [(256, 128), (160, 64)])
def test_quantized_psum_matches_numpy_simulation_int8(h, chunk):
    """t=2, even and ragged (160 = 2.5 × 64) hidden chunking: the compiled
    two-step equals the numpy oracle — the summed int8 payload recovered
    from the result is bitwise the oracle's (the reduce-scatter sum is
    exact by the qmax headroom); the final f32 dequant multiply is allowed
    one ULP of XLA-vs-numpy slack."""
    t = 2
    x = jax.random.normal(jax.random.PRNGKey(2), (t, 3, h), jnp.float32) * 2
    got = _run_quantized_psum(x, t, "int8", chunk)
    sim = _simulate(x, t, "int8", chunk)
    np.testing.assert_allclose(got, sim, rtol=2e-6, atol=2e-6)
    K = cm.quant_chunks(h, chunk)
    pad = ((0, 0), (0, K * chunk - h))
    scales = _sim_scales(x, t, "int8", chunk)

    def ints(arr):
        return np.rint(np.pad(arr, pad).reshape(3, K, chunk)
                       / scales[..., None])
    np.testing.assert_array_equal(ints(got), ints(sim))


@needs_pair
@pytest.mark.parametrize("h,chunk", [(256, 128), (192, 64)])
def test_quantized_psum_matches_numpy_simulation_int4(h, chunk):
    """t=2 packed-nibble path: the compiled a2a two-step equals the numpy
    oracle (quantize ±7 → exact block sum → requantize by t → dequant at
    scales·t) — the requantized int payload recovered from the result is
    bitwise the oracle's."""
    t = 2
    x = jax.random.normal(jax.random.PRNGKey(7), (t, 3, h), jnp.float32) * 2
    got = _run_quantized_psum(x, t, "int4", chunk)
    sim = _simulate(x, t, "int4", chunk)
    np.testing.assert_allclose(got, sim, rtol=2e-6, atol=2e-6)
    K = cm.quant_chunks(h, chunk)
    pad = ((0, 0), (0, K * chunk - h))
    scales = _sim_scales(x, t, "int4", chunk)

    def ints(arr):
        return np.rint(np.pad(arr, pad).reshape(3, K, chunk)
                       / (t * scales[..., None]))
    np.testing.assert_array_equal(ints(got), ints(sim))


@needs_pair
def test_quantized_psum_int4_rejects_unaligned_hidden():
    """h must divide 2t — the packed a2a ships byte-aligned h/t blocks."""
    t = 2
    x = jnp.zeros((t, 2, 130), jnp.float32)      # 130 % 4 != 0
    with pytest.raises(ValueError, match="2t"):
        _run_quantized_psum(x, t, "int4", 64)


@needs_pair
def test_quantized_psum_drift_bounded_vs_full_psum():
    """|quantized − full psum| ≤ t · scale/2 per chunk (each rank rounds
    at most half a step, summed across t ranks)."""
    t, h, chunk = 2, 256, 128
    x = jax.random.normal(jax.random.PRNGKey(3), (t, 4, h), jnp.float32)
    got = _run_quantized_psum(x, t, "int8", chunk)
    full = np.asarray(x, np.float32).sum(0)
    amax = np.abs(np.asarray(x)).reshape(t, 4, h // chunk, chunk) \
        .max(-1).max(0)
    scales = amax / collective_qmax("int8", t)
    err = np.abs(got - full).reshape(4, h // chunk, chunk)
    assert (err <= t * scales[..., None] / 2 + 1e-6).all()


def test_t1_is_identity_with_zero_quant_ops():
    """quant at t=1 must be a no-op: bitwise-identical logits and a decode
    module containing neither collectives nor any s8 op."""
    cfg = get_config("llama32-3b").reduced(num_layers=2)
    mesh = px.make_tp_mesh(1)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 2,
                              cfg.vocab_size)
    _, cache = px.tp_prefill(cfg, mesh, cache_w=12, unroll=True)(params, toks)
    tok = jnp.zeros((2,), jnp.int32)
    base = px.tp_decode_step(cfg, mesh, unroll=True)
    quant = px.tp_decode_step(cfg, mesh, unroll=True,
                              quant_collectives="int8")
    lb, _ = base(params, jax.tree.map(jnp.copy, cache), tok, jnp.int32(8))
    lq, _ = quant(params, jax.tree.map(jnp.copy, cache), tok, jnp.int32(8))
    np.testing.assert_array_equal(np.asarray(lb), np.asarray(lq))
    hlo = quant.lower(params, cache, tok, jnp.int32(8)).compile().as_text()
    assert parse_hlo_collectives(hlo) == []
    assert " s8[" not in hlo


# ---------------------------------------------------------------------------
# predicted == compiled under quantization (the house invariant)
# ---------------------------------------------------------------------------


def _decode_hlo(cfg, mesh, params, toks, t, unroll, quant):
    _, cache = px.tp_prefill(cfg, mesh, cache_w=12,
                             unroll=True)(params, toks)
    step = px.tp_decode_step(cfg, mesh, unroll=unroll,
                             quant_collectives=quant)
    tok = jnp.zeros((toks.shape[0],), jnp.int32)
    return step.lower(params, cache, tok,
                      jnp.int32(toks.shape[1])).compile().as_text()


def _predicted_decode(cfg, t, batch, quant):
    ops = cm.comm_ops_for(cfg, 1, 2, t, 1, b=4, batch=batch,
                          gather_mode="allgather", quant=quant)
    counts, wires = {}, {}
    for o in ops:
        if o.phase != "decode":
            continue
        counts[o.collective] = counts.get(o.collective, 0) + o.count
        wires[o.collective] = wires.get(o.collective, 0.0) + o.wire_bytes
    return counts, wires


@needs_mesh
@pytest.mark.parametrize("t", [2, 4])
@pytest.mark.parametrize("unroll", [True, False])
def test_tp_decode_hlo_counts_and_wire_bytes_match_prediction(t, unroll):
    """(t,1) both unroll modes: compiled decode-step collectives == the
    quantized commodel rows in COUNTS and WIRE BYTES (f32 configs, b=4).
    The scanned mode goes through hlo_comm's trip expansion, the unrolled
    one through the scatter-form reclassification — same answer."""
    cfg = get_config("llama32-3b").reduced(num_layers=2)
    mesh = px.make_tp_mesh(t)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 2,
                              cfg.vocab_size)
    hlo = _decode_hlo(cfg, mesh, params, toks, t, unroll, "int8")
    s = summarize(parse_hlo_collectives(hlo))
    got_counts = {k: v["count"] for k, v in s.items()}
    got_wires = {k: v["wire_bytes"] for k, v in s.items()}
    want_counts, want_wires = _predicted_decode(cfg, t, 2, "int8")
    assert got_counts == want_counts
    assert set(got_wires) == set(want_wires)
    for k in want_wires:
        assert got_wires[k] == pytest.approx(want_wires[k]), k
    # the decomposition itself: 2L 1-byte RS/AG pairs + 2L amax ARs + embed
    L = cfg.num_layers
    assert want_counts["reducescatter"] == 2 * L
    assert want_counts["allgather"] == 2 * L + 1
    assert want_counts["allreduce"] == 2 * L + 1


@needs_mesh
def test_tp_decode_hlo_counts_match_prediction_fp8():
    """fp8 keeps the same collective SCHEDULE; wire bytes are excluded on
    host CPU, where XLA upcasts the f8 payload (commodel models the
    accelerator's nominal 1-byte wire — DESIGN.md §12)."""
    cfg = get_config("llama32-3b").reduced(num_layers=2)
    mesh = px.make_tp_mesh(2)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 2,
                              cfg.vocab_size)
    hlo = _decode_hlo(cfg, mesh, params, toks, 2, True, "fp8")
    got = {k: v["count"]
           for k, v in summarize(parse_hlo_collectives(hlo)).items()}
    assert got == _predicted_decode(cfg, 2, 2, "fp8")[0]


@needs_mesh
@pytest.mark.parametrize("t", [2, 4])
def test_tp_decode_hlo_counts_and_wire_bytes_match_prediction_int4(t):
    """int4 (t,1): the compiled module shows the packed-nibble schedule —
    2L u8 all-to-alls + 2L u8 all-gathers at HALF-byte wire width, 2L f32
    amax ARs + the full-width embed AR — matching the commodel rows in
    counts AND wire bytes (the u8 payload needs no upcast, so bytes check
    exactly even on host CPU, unlike fp8)."""
    cfg = get_config("llama32-3b").reduced(num_layers=2)
    mesh = px.make_tp_mesh(t)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 2,
                              cfg.vocab_size)
    hlo = _decode_hlo(cfg, mesh, params, toks, t, True, "int4")
    s = summarize(parse_hlo_collectives(hlo))
    got_counts = {k: v["count"] for k, v in s.items()}
    got_wires = {k: v["wire_bytes"] for k, v in s.items()}
    want_counts, want_wires = _predicted_decode(cfg, t, 2, "int4")
    assert got_counts == want_counts
    for k in want_wires:
        assert got_wires[k] == pytest.approx(want_wires[k]), k
    L = cfg.num_layers
    assert want_counts["alltoall"] == 2 * L
    assert want_counts["allgather"] == 2 * L + 1
    assert want_counts["allreduce"] == 2 * L + 1
    assert "reducescatter" not in want_counts


def test_closed_form_ratio_int4_flash_communication_target():
    """Production configs at bf16: the packed 4-bit payload lands the
    Flash-Communication ~0.28× headline — always < 0.35× and strictly
    below the int8 two-step's ratio."""
    for arch in ("llama32-3b", "llama31-8b", "llama2-13b"):
        h = get_config(arch).d_model
        for t in (2, 4, 8):
            r4 = cm.quant_ar_wire_ratio(h, t, quant="int4", b=2)
            assert r4 < 0.35, (arch, t, r4)
            assert r4 < cm.quant_ar_wire_ratio(h, t, quant="int8", b=2)
    assert cm.quant_ar_wire_ratio(3072, 2, quant="int4", b=2) == \
        pytest.approx(0.265625)


@needs_mesh
@pytest.mark.parametrize("unroll", [True, False])
def test_quant_hybrid_stage_hlo_matches_prediction(unroll):
    """(2,2) both unroll modes: every stage of the quantized hybrid engine
    compiles to exactly hybrid_stage_collectives(quant='int8')."""
    cfg = get_config("llama32-3b").reduced(num_layers=2)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 2,
                              cfg.vocab_size)
    eng = px.PipelineEngine(cfg, t=2, p=2, unroll=unroll,
                            quant_collectives="int8")
    staged = eng.prepare(params)
    _, caches = eng.prefill_with_cache(staged, toks, 12)
    tok0 = jnp.zeros((2,), jnp.int32)
    for s in range(2):
        hlo = eng.stage_decode_hlo(staged, caches, tok0, 8, s)
        got = {k: v["count"]
               for k, v in summarize(parse_hlo_collectives(hlo)).items()}
        assert got == cm.hybrid_stage_collectives(cfg, 2, 2, s,
                                                  quant="int8"), (s, unroll)


def test_closed_form_ratio_under_acceptance_bound_full_configs():
    """Production configs at bf16: int8 payload + f32 scales < 0.6× the
    bf16 allreduce wire for every TP degree — and t-invariant."""
    for arch in ("llama32-3b", "llama31-8b", "llama2-13b"):
        h = get_config(arch).d_model
        ratios = [cm.quant_ar_wire_ratio(h, t, quant="int8", b=2)
                  for t in (2, 4, 8)]
        assert all(r < 0.6 for r in ratios), (arch, ratios)
        assert ratios[0] == ratios[1] == ratios[2]
    assert cm.quant_ar_wire_ratio(3072, 2, quant="int8", b=2) == \
        pytest.approx(0.515625)


# ---------------------------------------------------------------------------
# runtime + slo + planner threading
# ---------------------------------------------------------------------------


@needs_pair
def test_backend_decode_comm_ops_decomposed():
    cfg = get_config("llama32-3b").reduced(num_layers=2)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    from repro.runtime.backends import make_backend
    be = make_backend("tp", cfg, params, num_slots=2, max_len=16, t=2,
                      quant_collectives="int8")
    kinds = {o.collective for o in be.decode_comm_ops()}
    assert {"allreduce", "reducescatter", "allgather"} <= kinds
    one_byte = [o for o in be.decode_comm_ops()
                if o.dtype_bytes == 1
                and o.collective in ("reducescatter", "allgather")]
    assert sum(o.count for o in one_byte) == 2 * 2 * cfg.num_layers


def test_backend_rejections():
    """quant composes with the explicit engines only: paged attention and
    the gspmd backend both refuse the knob loudly."""
    cfg = get_config("llama32-3b").reduced(num_layers=2)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    from repro.runtime.backends import make_backend
    with pytest.raises(ValueError, match="paged"):
        make_backend("tp", cfg, params, num_slots=2, max_len=16, t=2,
                     paged=True, quant_collectives="int8")
    with pytest.raises(ValueError, match="GSPMD"):
        make_backend("gspmd", cfg, params, num_slots=2, max_len=16,
                     quant_collectives="int8")
    with pytest.raises(ValueError, match="unknown quant"):
        make_backend("tp", cfg, params, num_slots=2, max_len=16, t=2,
                     quant_collectives="int2")


def test_slo_quant_lowers_volume_never_hurts_tpot():
    """For every TP degree the quantized prediction moves strictly fewer
    decode bytes and never predicts a slower effective tpot (the two-step
    is charged one α — Flash Communication's fused launch, DESIGN.md §12)."""
    from repro.core.slo import predict_slo
    cfg = get_config("llama31-8b")
    for t in (2, 4, 8):
        base = predict_slo(cfg, 64, 256, t=t, p=1)
        q = predict_slo(cfg, 64, 256, t=t, p=1, quant="int8")
        assert q.comm_volume < base.comm_volume, t
        assert q.breakdown["tpot_effective"] <= \
            base.breakdown["tpot_effective"] + 1e-9, t
    assert predict_slo(cfg, 64, 256, t=1, p=1, quant="int8").comm_volume \
        == predict_slo(cfg, 64, 256, t=1, p=1).comm_volume


def test_planner_quant_reenters_volume_budget_frontier():
    """A 250 MiB fabric budget prices TP=8 off the frontier at full width
    (≈291 MiB) — quantized (≈183 MiB) it re-enters and wins TTFT, the
    Flash-Communication shape the planner docstring promises."""
    from repro.core.planner import plan
    cfg = get_config("llama31-8b")
    budget = 250 * 2 ** 20
    base = plan(cfg, 8, 64, 256, objective="ttft", volume_budget=budget)
    quant = plan(cfg, 8, 64, 256, objective="ttft", volume_budget=budget,
                 quant="int8")
    base_tp8 = next(c for c in base if c.tensor_parallel == 8)
    assert base_tp8.score == float("inf")
    assert quant[0].tensor_parallel == 8
    assert quant[0].score < float("inf")
    # and quant never *adds* volume on any candidate
    qvol = {c.name: c.slo.comm_volume for c in quant}
    for c in base:
        assert qvol[c.name] <= c.slo.comm_volume + 1e-6
