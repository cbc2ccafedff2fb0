"""PP/hybrid decode subsystem: per-stage KV caches, pipelined generation,
layer-partition and logit-mask regressions, and measured-vs-predicted decode
communication parity (Eq. 2 / Table V decode rows, per-stage HLO counts)."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import commodel as cm
from repro.core import parallel_exec as px
from repro.core.hlo_comm import parse_hlo_collectives, summarize
from repro.models.transformer import get_model
from repro.runtime.engine import InferenceEngine

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 host-platform devices")

B, S_P, N_GEN = 2, 8, 5


def _setup(num_layers=4):
    cfg = get_config("llama32-3b").reduced(num_layers=num_layers)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S_P), 2,
                              cfg.vocab_size)
    return cfg, params, toks


# ---------------------------------------------------------------------------
# satellite: uneven layer partition
# ---------------------------------------------------------------------------


def test_stage_layer_partition_covers_all_layers():
    """Indivisible layer counts must not silently drop layers (28 @ p=8
    used to run only 24)."""
    for L, p in [(28, 8), (5, 2), (7, 3), (9, 4), (32, 8)]:
        sizes = cm.stage_layer_partition(L, p)
        assert sum(sizes) == L
        assert max(sizes) - min(sizes) <= 1
        assert sizes == sorted(sizes, reverse=True)   # remainder goes early
    cfg = get_config("llama32-3b").reduced(num_layers=28)
    ranges = [px.stage_layer_range(cfg, 8, s) for s in range(8)]
    assert ranges[0] == (0, 4)
    assert ranges[-1] == (25, 28)
    for (lo, hi), (lo2, _) in zip(ranges, ranges[1:]):
        assert hi == lo2                              # contiguous cover
    assert ranges[-1][1] == 28


@needs_mesh
def test_uneven_layer_split_forward_matches_single_stage():
    """Regression: p=2/p=3 over 5 layers must equal the single-stage run
    (the old L//p split executed only 4 of the 5 layers)."""
    cfg, params, toks = _setup(num_layers=5)
    ref_eng = px.PipelineEngine(cfg, t=1, p=1)
    ref = np.asarray(ref_eng.forward(ref_eng.prepare(params), toks))
    for p in (2, 3):
        eng = px.PipelineEngine(cfg, t=1, p=p)
        out = np.asarray(eng.forward(eng.prepare(params), toks))
        np.testing.assert_allclose(ref, out, atol=2e-4)


# ---------------------------------------------------------------------------
# satellite: pad-vocab logit mask dtype
# ---------------------------------------------------------------------------


def test_pad_logit_mask_keeps_bf16_dtype():
    """Masking pad-vocab columns must not promote bf16 logits to f32 (nor
    overflow to -inf): the mask value is finfo(logits.dtype).min."""
    cfg = dataclasses.replace(get_config("llama32-3b").reduced(),
                              vocab_size=500, dtype="bfloat16")
    assert cfg.padded_vocab == 512                    # masking active
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S_P), 2,
                              cfg.vocab_size)
    logits, _, _ = model.prefill(params, toks, max_len=32)
    assert logits.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert (np.asarray(jnp.argmax(logits, -1)) < cfg.vocab_size).all()


@needs_mesh
def test_pad_logit_mask_keeps_bf16_dtype_explicit_engines():
    cfg = dataclasses.replace(get_config("llama32-3b").reduced(),
                              vocab_size=500, dtype="bfloat16")
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S_P), 2,
                              cfg.vocab_size)
    logits, _ = px.tp_prefill(cfg, px.make_tp_mesh(4))(params, toks)
    assert logits.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    eng = px.PipelineEngine(cfg, t=1, p=2)            # dense last-stage head
    out = eng.forward(eng.prepare(params), toks)
    assert out.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


# ---------------------------------------------------------------------------
# tentpole: decode parity across engines
# ---------------------------------------------------------------------------


@needs_mesh
@pytest.mark.parametrize("unroll", [True, False])
@pytest.mark.parametrize("t,p", [(1, 2), (2, 2)])
def test_pipeline_generate_matches_tp_and_inference_engine(t, p, unroll):
    """Greedy tokens from PP/hybrid generate == TP engine == InferenceEngine
    on the same params (ISSUE decode-parity criterion)."""
    cfg, params, toks = _setup()
    mesh = px.make_tp_mesh(4)
    logits, cache = px.tp_prefill(cfg, mesh, cache_w=32,
                                  unroll=True)(params, toks)
    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
    ref, _ = px.tp_generate(cfg, mesh, N_GEN)(params, cache, tok0,
                                              jnp.int32(S_P))
    ref = np.asarray(ref)

    ie = InferenceEngine(cfg, params, max_len=64, decode_chunk=1)
    ie_out = np.asarray(ie.generate(toks, max_new_tokens=N_GEN + 1))
    np.testing.assert_array_equal(ie_out[:, 0], np.asarray(tok0))
    np.testing.assert_array_equal(ie_out[:, 1:], ref)

    eng = px.PipelineEngine(cfg, t=t, p=p, unroll=unroll)
    staged = eng.prepare(params)
    lg, caches = eng.prefill_with_cache(staged, toks, cache_w=32)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(lg, -1)),
                                  np.asarray(tok0))
    out, _ = eng.generate(staged, caches, tok0, S_P, N_GEN)
    np.testing.assert_array_equal(np.asarray(out), ref)


@needs_mesh
def test_pipeline_generate_uneven_layers():
    """Decode over an indivisible layer split stays token-identical to the
    fused TP path (all 5 layers' caches exercised)."""
    cfg, params, toks = _setup(num_layers=5)
    mesh = px.make_tp_mesh(4)
    logits, cache = px.tp_prefill(cfg, mesh, cache_w=32,
                                  unroll=True)(params, toks)
    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
    ref, _ = px.tp_generate(cfg, mesh, N_GEN)(params, cache, tok0,
                                              jnp.int32(S_P))
    eng = px.PipelineEngine(cfg, t=2, p=2, unroll=False)
    staged = eng.prepare(params)
    _, caches = eng.prefill_with_cache(staged, toks, cache_w=32)
    out, _ = eng.generate(staged, caches, tok0, S_P, N_GEN)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@needs_mesh
def test_pipeline_decode_cache_donated_on_fast_path():
    cfg, params, toks = _setup()
    eng = px.PipelineEngine(cfg, t=1, p=2, unroll=False)
    staged = eng.prepare(params)
    logits, caches = eng.prefill_with_cache(staged, toks, cache_w=32)
    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
    _, new_caches = eng.decode_once(staged, caches, tok0, S_P)
    assert all(c["k"].is_deleted() and c["v"].is_deleted() for c in caches)
    shapes = [c["k"].shape for c in new_caches]
    assert shapes == [(2, B, 32, cfg.num_kv_heads, cfg.head_dim)] * 2


# ---------------------------------------------------------------------------
# tentpole: measured decode communication == analytical predictions
# ---------------------------------------------------------------------------

LAYOUTS = [(1, 2), (1, 4), (2, 2)]


@needs_mesh
@pytest.mark.parametrize("t,p", LAYOUTS)
def test_decode_transfers_match_comm_model(t, p):
    """TransferRecords logged by generate == pp/hybrid_comm_ops decode send
    rows: count (p-1)·2·(s_d-1) and exact bytes (f32 host platform, b=4)."""
    cfg, params, toks = _setup()
    eng = px.PipelineEngine(cfg, t=t, p=p, unroll=False)
    staged = eng.prepare(params)
    logits, caches = eng.prefill_with_cache(staged, toks, cache_w=32)
    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
    eng.generate(staged, caches, tok0, S_P, N_GEN)

    s_d = N_GEN + 1                   # prefill emits decoded token #1
    if t == 1:
        ops = cm.pp_comm_ops(cfg, S_P, s_d, p, b=4, batch=B)
    else:
        ops = cm.hybrid_comm_ops(cfg, S_P, s_d, t, p, b=4, batch=B,
                                 gather_mode="allgather")
    for phase in ("prefill", "decode"):
        want = [o for o in ops
                if o.collective == "send" and o.phase == phase][0]
        got = eng.transfer_summary(phase=phase)
        assert got["count"] == want.count
        assert got["bytes"] == want.total_msg_bytes


@needs_mesh
@pytest.mark.parametrize("unroll", [True, False])
def test_hybrid_stage_decode_hlo_matches_prediction(unroll):
    """Per-stage decode HLO collective counts == hybrid_stage_collectives,
    including an uneven 5-layer split (stage 0: 2·3+1 AR; stage 1: 2·2 AR +
    2 redistribute all-gathers + 1 logits all-gather)."""
    cfg, params, toks = _setup(num_layers=5)
    eng = px.PipelineEngine(cfg, t=2, p=2, unroll=unroll)
    staged = eng.prepare(params)
    logits, caches = eng.prefill_with_cache(staged, toks, cache_w=16)
    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
    for s in range(2):
        hlo = eng.stage_decode_hlo(staged, caches, tok0, S_P, s)
        got = {k: v["count"]
               for k, v in summarize(parse_hlo_collectives(hlo)).items()}
        assert got == cm.hybrid_stage_collectives(cfg, 2, 2, s)
    assert cm.hybrid_stage_collectives(cfg, 2, 2, 0) == {"allreduce": 7}
    assert cm.hybrid_stage_collectives(cfg, 2, 2, 1) == {"allreduce": 4,
                                                         "allgather": 3}


@needs_mesh
def test_pure_pp_decode_stage_hlo_has_no_collectives():
    """t=1 stages are single-device: decode must move data only over the
    logged boundary transfers, never via in-module collectives."""
    cfg, params, toks = _setup()
    eng = px.PipelineEngine(cfg, t=1, p=2, unroll=False)
    staged = eng.prepare(params)
    logits, caches = eng.prefill_with_cache(staged, toks, cache_w=16)
    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
    for s in range(2):
        hlo = eng.stage_decode_hlo(staged, caches, tok0, S_P, s)
        assert parse_hlo_collectives(hlo) == []


def test_hybrid_comm_ops_uneven_split_counts():
    """hybrid_comm_ops' per-stage allreduce count follows the uneven split
    (stage-0 rank view) and reduces to 2L/p + 1 when p divides L."""
    cfg = get_config("llama31-8b")                    # L=32
    even = cm.hybrid_comm_ops(cfg, 128, 128, 2, 2)
    ar = [o for o in even if o.collective == "allreduce"
          and o.phase == "prefill"][0]
    assert ar.count == 33                             # unchanged, 2·32/2 + 1
    cfg5 = dataclasses.replace(cfg, num_layers=5)
    odd = cm.hybrid_comm_ops(cfg5, 128, 128, 2, 2)
    ar = [o for o in odd if o.collective == "allreduce"
          and o.phase == "prefill"][0]
    assert ar.count == 2 * 3 + 1                      # stage 0 owns 3 layers
    # op-level sum must still equal the closed form on indivisible L
    comp = cm.v_hybrid_components(cfg5, 128, 128, 2, 2)
    got_ar = sum(o.wire_bytes for o in odd if o.collective == "allreduce")
    assert got_ar == pytest.approx(comp["allreduce"], rel=1e-12)
    assert cm.total_volume(odd) == pytest.approx(
        cm.v_hybrid(cfg5, 128, 128, 2, 2), rel=1e-12)


@needs_mesh
@pytest.mark.parametrize("t,p", [(1, 4), (2, 2)])
def test_prepare_places_only_each_stages_share(t, p):
    """Each stage holds its own layer slice on its own devices, the
    embedding only on the first stage and the head only on the last — no
    stage holds the whole model."""
    cfg, params, _ = _setup(num_layers=4)
    eng = px.PipelineEngine(cfg, t=t, p=p, unroll=False)
    staged = eng.prepare(params)
    for s, part in enumerate(staged):
        lo, hi = px.stage_layer_range(cfg, p, s)
        assert set(part) == ({"blocks"} | ({"embed"} if s == 0 else set())
                             | ({"final_norm", "lm_head"}
                                if s == p - 1 else set()))
        np.testing.assert_array_equal(np.asarray(part["blocks"]["wq"]),
                                      np.asarray(params["blocks"]["wq"][lo:hi]))
        devs = set(eng.meshes[s].devices.flat)
        for leaf in jax.tree.leaves(part):
            assert leaf.sharding.device_set == devs
