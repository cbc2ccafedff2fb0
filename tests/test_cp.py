"""Context parallelism: sequence-sharded prefill (ISSUE 5 acceptance).

1. Token identity: CP-sharded prefill through the scheduler is
   token-identical to the cp=1 path and to isolated serving on ragged
   traces at (t, c, p) ∈ {(1,2,1), (2,2,1), (1,2,2)} — contiguous slot
   caches AND paged pools (gather-into-slots / gather-into-pages handoff).
2. Counts: per-layer CP ring counts and bytes match
   ``commodel.cp_comm_ops``, the compiled HLO of the CP prefill (both
   unroll modes, scans trip-expanded), the per-stage prefill modules
   (``hybrid_stage_collectives(..., c, phase="prefill")``), and — for the
   PP hops — the measured TransferRecords at the [S/c, h/t] per-worker
   shard.
3. Decode is untouched: same per-step collective schedule and predictions
   at any c (CP is prefill-only, DESIGN.md §9).
4. Guards: gspmd rejects c>1, chunked prefill rejects c>1 backends,
   CP-padded prompts respect max_len.
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
from repro.models import layers
from repro.models.transformer import get_model
from repro.runtime.backends import make_backend
from repro.runtime.engine import InferenceEngine
from repro.runtime.request import Request
from repro.runtime.scheduler import Scheduler, VirtualClock

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 host-platform devices")
needs_pair = pytest.mark.skipif(len(jax.devices()) < 2,
                                reason="needs 2 host-platform devices")

MAX_LEN = 64
PAGE = 8

# (t, c, p) acceptance layouts; (1,2,1) runs on 2 devices, the rest on 4
LAYOUTS = [("tp", dict(t=1, c=2), 2),
           ("tp", dict(t=2, c=2), 4),
           ("pp", dict(t=1, c=2, p=2), 4)]


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama32-3b").reduced(num_layers=2)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _ragged_requests(cfg):
    rng = np.random.default_rng(0)
    lens = [(7, 6), (11, 4), (5, 8), (9, 3)]   # odd lengths force padding
    return [Request(rid=i,
                    prompt=rng.integers(2, cfg.vocab_size, s).astype(np.int32),
                    max_new_tokens=n)
            for i, (s, n) in enumerate(lens)]


def _solo_reference(cfg, params, req):
    eng = InferenceEngine(cfg, params, max_len=MAX_LEN, decode_chunk=1)
    out = eng.generate(jnp.asarray(req.prompt)[None, :],
                       max_new_tokens=req.max_new_tokens)
    return np.asarray(out)[0].tolist()


def _count(ops, phase=None):
    counts = {}
    for o in ops:
        if phase in (None, o.phase):
            counts[o.collective] = counts.get(o.collective, 0) + o.count
    return counts


def _hlo_counts(hlo: str):
    return {k: v["count"]
            for k, v in summarize(parse_hlo_collectives(hlo)).items()}


# ---------------------------------------------------------------------------
# the ring primitive: assembly is bitwise, in absolute order
# ---------------------------------------------------------------------------


@needs_pair
def test_ring_kv_assemble_is_bitwise_and_ordered():
    """Every cp worker assembles the full [B, S, H, D] tensor, bitwise
    equal to the unsharded input, with blocks at their absolute offsets."""
    c = 2
    mesh = px.make_tp_cp_mesh(1, c)
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((2, 8, 3, 4)), jnp.float32)

    # replicated out spec: each worker's assembled copy must equal the
    # unsharded input bitwise — blocks landed at their absolute offsets
    fn_full = jax.shard_map(lambda b: layers.ring_kv_assemble(b, "cp", c),
                            mesh=mesh, in_specs=P(None, "cp"),
                            out_specs=P(None, None), check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(fn_full)(k)),
                                  np.asarray(k))

    # per-worker view: worker w's own block of its assembled copy is the
    # input's rows [w*S/c, (w+1)*S/c) — absolute order, not arrival order
    def own_block(b):
        full = layers.ring_kv_assemble(b, "cp", c)
        idx = jax.lax.axis_index("cp")
        s_loc = b.shape[1]
        return jax.lax.dynamic_slice_in_dim(full, idx * s_loc, s_loc, axis=1)

    fn_own = jax.shard_map(own_block, mesh=mesh, in_specs=P(None, "cp"),
                           out_specs=P(None, "cp"), check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(fn_own)(k)),
                                  np.asarray(k))


@needs_pair
def test_block_level_cp_branch_matches_plain_attention(setup):
    """``blocks.dense_block_apply(cp_axis=...)`` — the block-level CP API
    — produces the same outputs and seeded cache as the unsharded block:
    the ring assembles K/V bitwise, so only the shard split differs."""
    from repro.models import blocks
    cfg, params = setup
    c = 2
    pl = {k: np.asarray(v[0]) for k, v in params["blocks"].items()}
    pl = {k: jnp.asarray(v) for k, v in pl.items()}
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 8, cfg.d_model)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(8), (2, 8))
    ref, ref_cache, _ = blocks.dense_block_apply(
        cfg, pl, x, positions, layers.make_mask(8, 8), build_cache_w=16)

    mesh = px.make_tp_cp_mesh(1, c)

    def fn(pl, x, positions):
        s_loc = x.shape[1]
        off = jax.lax.axis_index("cp") * s_loc
        mask = layers.make_mask(s_loc, c * s_loc, q_offset=off)
        y, cache, _ = blocks.dense_block_apply(
            cfg, pl, x, off + positions[:, :s_loc], mask,
            build_cache_w=16, cp_axis="cp", cp_size=c)
        return y, cache

    specs = jax.tree.map(lambda _: P(), pl)
    mapped = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(specs, P(None, "cp"), P(None, None)),
        out_specs=(P(None, "cp"), {"k": P(), "v": P()}),
        check_vma=False))
    got, got_cache = mapped(pl, x, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)
    # the ring assembly itself is bitwise; the projection matmul on the
    # [S/c] shard tiles differently, leaving ~1e-7 noise in the cache
    for key in ("k", "v"):
        np.testing.assert_allclose(np.asarray(got_cache[key]),
                                   np.asarray(ref_cache[key]), atol=1e-5)


# ---------------------------------------------------------------------------
# analytical model: cp_comm_ops shapes and composition
# ---------------------------------------------------------------------------


def test_cp_comm_ops_counts_and_bytes(setup):
    cfg, _ = setup
    L, h = cfg.num_layers, cfg.d_model
    for s_p, c, t in [(8, 2, 1), (8, 2, 2), (128, 4, 2), (7, 2, 1)]:
        ops = cm.cp_comm_ops(cfg, s_p, c, t=t)
        ring = [o for o in ops if o.collective == "collectivepermute"][0]
        ar = [o for o in ops if o.collective == "allreduce"][0]
        shard = -(-s_p // c)
        assert ring.count == 2 * L * (c - 1)
        assert ring.shape == (shard, (cfg.num_kv_heads // t) * cfg.head_dim)
        assert ring.workers == c
        # ring hops are charged 1x wire (every rank ships its block)
        assert ring.wire_bytes == ring.total_msg_bytes
        assert ar.count == 1 and ar.shape == (1, h) and ar.workers == c
    assert cm.cp_comm_ops(cfg, 128, 1) == []


def test_comm_ops_for_composes_cp(setup):
    """c>1 shrinks the TP/PP prefill rows to the ceil(s_p/c) shard, adds
    the ring rows, and leaves every decode row untouched."""
    cfg, _ = setup
    base = cm.comm_ops_for(cfg, 4, 5, 2, 2, gather_mode="allgather")
    with_cp = cm.comm_ops_for(cfg, 8, 5, 2, 2, c=2,
                              gather_mode="allgather")
    dec = [o for o in base if o.phase == "decode"]
    dec_cp = [o for o in with_cp if o.phase == "decode"]
    assert dec == dec_cp
    # prefill TP rows at s_p=8, c=2 == the c=1 rows at s_p=4
    pre = [o for o in base if o.phase == "prefill"]
    pre_cp = [o for o in with_cp if o.phase == "prefill"
              if o.collective not in ("collectivepermute",)
              and not (o.collective == "allreduce" and o.workers == 2
                       and o.shape == (1, cfg.d_model))]
    assert pre == pre_cp


# ---------------------------------------------------------------------------
# acceptance 1: CP token-identical to cp=1 and solo on ragged traces
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,kw,ndev", LAYOUTS)
@pytest.mark.parametrize("paged", [False, True])
def test_cp_token_identity_on_ragged_traces(setup, kind, kw, ndev, paged):
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs {ndev} host-platform devices")
    cfg, params = setup
    reqs = _ragged_requests(cfg)
    refs = {r.rid: _solo_reference(cfg, params, r) for r in reqs}
    backend = make_backend(kind, cfg, params, num_slots=2, max_len=MAX_LEN,
                           paged=paged, page_size=PAGE, **kw)
    got = Scheduler(backend, clock=VirtualClock()).run(
        _ragged_requests(cfg)).tokens_by_rid()
    for r in reqs:
        assert got[r.rid] == refs[r.rid], \
            f"cp {kind}{kw} paged={paged}: request {r.rid} diverged"
    if paged:
        # every page returned: the padded prefill stayed inside its slot's
        # own pages and eviction freed them all
        assert backend.pool.stats().used_tokens == 0
        assert backend.pool.free_pages == backend.pool.num_pages - 1


@needs_mesh
def test_cp_engine_generate_matches_tp_engine(setup):
    """Engine level, no scheduler: (2,2,1) cp prefill + fused decode equals
    the plain t=2 engine token for token, both unroll modes."""
    cfg, params = setup
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 2,
                              cfg.vocab_size)
    mesh_ref = px.make_tp_mesh(2)
    logits, cache = px.tp_prefill(cfg, mesh_ref, cache_w=32)(params, toks)
    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
    ref, _ = px.tp_generate(cfg, mesh_ref, 5)(params, cache, tok0,
                                              jnp.int32(8))
    for unroll in (True, False):
        mesh = px.make_tp_cp_mesh(2, 2)
        lg, cc = px.cp_prefill(cfg, mesh, cache_w=32,
                               unroll=unroll)(params, toks, jnp.int32(7))
        np.testing.assert_array_equal(np.asarray(jnp.argmax(lg, -1)),
                                      np.asarray(tok0))
        # decode on the SAME (tp, cp) mesh consumes the cp-seeded cache;
        # feed the token as an uncommitted host array (tok0 lives on the
        # 2-device reference mesh)
        out, _ = px.tp_generate(cfg, mesh, 5)(params, cc,
                                              np.asarray(tok0),
                                              jnp.int32(8))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# acceptance 2: ring counts/bytes == commodel == compiled HLO == measured
# ---------------------------------------------------------------------------


@needs_pair
@pytest.mark.parametrize("t,ndev", [(1, 2), (2, 4)])
def test_cp_prefill_hlo_matches_commodel(setup, t, ndev):
    """(1,2,1)/(2,2,1): the CP prefill module shows exactly the predicted
    schedule — ring permutes + cp allreduce (+ TP rows at the shard) —
    with matching message bytes, in both unroll modes."""
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs {ndev} host-platform devices")
    cfg, params = setup
    c, s_p = 2, 8
    backend = make_backend("tp", cfg, params, num_slots=2, max_len=MAX_LEN,
                           t=t, c=c)
    want_ops = backend.prefill_comm_ops(s_p)
    want = _count(want_ops)
    for unroll in (True, False):
        fn = px.cp_prefill(cfg, backend.mesh, cache_w=backend.cache_w,
                           unroll=unroll)
        hlo = fn.lower(params, jax.ShapeDtypeStruct((1, s_p), jnp.int32),
                       jax.ShapeDtypeStruct((), jnp.int32)) \
                .compile().as_text()
        colls = parse_hlo_collectives(hlo)
        assert _hlo_counts(hlo) == want, (t, unroll)
        # ring bytes: HLO permutes move exactly the predicted KV blocks
        # (f32 host platform — predict at b=4)
        pred_ring = [o for o in cm.cp_comm_ops(cfg, s_p, c, t=t, b=4)
                     if o.collective == "collectivepermute"][0]
        got_ring = [x for x in colls if x.kind == "collectivepermute"]
        assert sum(x.total_bytes for x in got_ring) == \
            pred_ring.total_msg_bytes
        assert sum(x.wire_bytes for x in got_ring) == pred_ring.wire_bytes
    # the backend's own prefill_hlo agrees
    assert _hlo_counts(backend.prefill_hlo(s_p)) == want


@needs_mesh
def test_cp_pp_stage_hlo_and_measured_transfers(setup):
    """(1,2,2): per-stage prefill HLO == hybrid_stage_collectives(c=2,
    phase="prefill"); decode stages stay collective-free; the boundary
    hop measured by TransferRecords carries the [S/c, h/t] per-worker
    shard commodel predicts."""
    cfg, params = setup
    t, c, p = 1, 2, 2
    backend = make_backend("pp", cfg, params, num_slots=2, max_len=MAX_LEN,
                           t=t, c=c, p=p)
    toks = jnp.zeros((1, 8), jnp.int32)
    for s in range(p):
        hlo = backend.engine.stage_hlo(backend.staged, toks, s)
        assert _hlo_counts(hlo) == cm.hybrid_stage_collectives(
            cfg, t, p, s, c=c, phase="prefill"), s
        # decode modules: replicated over cp, still zero collectives
        dec = backend.stage_decode_hlo(s)
        assert parse_hlo_collectives(dec) == []

    reqs = _ragged_requests(cfg)
    backend.drain_transfers()
    Scheduler(backend, clock=VirtualClock()).run(reqs)
    # replay: per request one prefill with (p-1)·2 hops of the padded
    # [1, ceil(s_p/c), h/t] pair — phase-filtered engine log
    want_count = sum((p - 1) * 2 for _ in reqs)
    want_bytes = sum(
        [o for o in backend.prefill_comm_ops(r.prompt_len)
         if o.collective == "send"][0].total_msg_bytes
        for r in reqs)
    got = backend.engine.transfer_summary(phase="prefill")
    assert got["count"] == want_count
    assert got["bytes"] == want_bytes


@needs_pair
@pytest.mark.parametrize("kind,kw,ndev", LAYOUTS)
def test_cp_decode_schedule_unchanged(setup, kind, kw, ndev):
    """CP is prefill-only: the decode step's predicted ops equal the c=1
    backend's, and (for the TP kinds) the compiled decode module shows the
    c=1 schedule."""
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs {ndev} host-platform devices")
    cfg, params = setup
    backend = make_backend(kind, cfg, params, num_slots=2, max_len=MAX_LEN,
                           paged=False, **kw)
    base_kw = dict(kw)
    base_kw["c"] = 1
    if kind == "tp" and base_kw.get("t", 1) < 2:
        base_kw["t"] = 2            # tp kind needs a non-degenerate layout
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
    base = make_backend(kind, cfg, params, num_slots=2, max_len=MAX_LEN,
                        **base_kw)
    if kw.get("t", 1) == base_kw.get("t", 1):
        assert _count(backend.decode_comm_ops()) == \
            _count(base.decode_comm_ops())
    if kind == "tp":
        want = ({"allreduce": 2 * cfg.num_layers + 1, "allgather": 1}
                if kw.get("t", 1) > 1 else {})
        assert _hlo_counts(backend.decode_step_hlo()) == want


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------


def test_cp_guards(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="explicit engines"):
        make_backend("gspmd", cfg, params, num_slots=2, c=2)
    with pytest.raises(ValueError, match="t >= 2 or c >= 2"):
        make_backend("tp", cfg, params, num_slots=2, t=1, c=1)


@needs_pair
def test_cp_rejects_chunked_prefill(setup):
    cfg, params = setup
    backend = make_backend("tp", cfg, params, num_slots=2, max_len=MAX_LEN,
                           t=1, c=2, paged=True, page_size=PAGE)
    with pytest.raises(ValueError, match="alternative"):
        Scheduler(backend, clock=VirtualClock(), chunk_size=4)


@needs_pair
def test_cp_sliding_window_serves_past_max_len(setup):
    """A sliding-window model serves prompts beyond max_len (the ring
    cache keeps the last W positions) — the CP padding guard must honor
    the same waiver the scheduler's admission check grants, and stay
    token-identical to the c=1 path."""
    import dataclasses
    cfg, _ = setup
    swa = dataclasses.replace(cfg, sliding_window=16)
    params = get_model(swa).init(jax.random.PRNGKey(0))
    req = Request(rid=0,
                  prompt=np.random.default_rng(3).integers(
                      2, swa.vocab_size, 41).astype(np.int32),
                  max_new_tokens=3)
    ref = make_backend("tp", swa, params, num_slots=1, max_len=32, t=2)
    want = Scheduler(ref, clock=VirtualClock()).run(
        [dataclasses.replace(req)]).tokens_by_rid()[0]
    cp = make_backend("tp", swa, params, num_slots=1, max_len=32, t=1, c=2)
    got = Scheduler(cp, clock=VirtualClock()).run(
        [dataclasses.replace(req)]).tokens_by_rid()[0]
    assert got == want


@needs_pair
def test_cp_padded_prompt_respects_max_len(setup):
    cfg, params = setup
    backend = make_backend("tp", cfg, params, num_slots=1, max_len=8,
                           t=1, c=2)
    sched = Scheduler(backend, clock=VirtualClock())
    # 7-token prompt pads to 8; with max_new_tokens=2 the cache needs
    # max(7+1, 8) = 8 positions — exactly fits
    sched.run([Request(rid=0, prompt=np.arange(2, 9, dtype=np.int32),
                       max_new_tokens=2)])
    with pytest.raises(ValueError, match="cache positions"):
        sched.submit(Request(rid=1, prompt=np.arange(2, 10, dtype=np.int32),
                             max_new_tokens=2))
