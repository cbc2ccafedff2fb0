"""Paged KV cache + chunked prefill (ISSUE 4 acceptance criteria).

1. Token identity: paged backends (chunked prefill + paged decode through
   the scheduler) match the contiguous backends AND isolated serving on
   ragged traces at (t, p) ∈ {(1,1), (2,1), (1,2), (2,2)}.
2. Counts: per-chunk prefill and per-step decode collective counts match
   ``commodel`` (``chunked_prefill_ops`` / ``comm_ops_for``) and the
   compiled HLO of the paged passes; PP chunk boundary hops measured ==
   predicted bytes.
3. The paged Pallas kernel (direct page indexing via scalar-prefetched
   block tables) matches the gather-based oracle.
4. Scheduler fix: iterations with no decoding slot never invoke the jitted
   decode step.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import commodel as cm
from repro.core.hlo_comm import parse_hlo_collectives, summarize
from repro.kernels.decode_attention.paged_kernel import \
    paged_decode_attention_pallas
from repro.kernels.decode_attention.ref import paged_decode_attention_ref
from repro.models import layers
from repro.models.transformer import get_model
from repro.runtime.backends import make_backend
from repro.runtime.engine import InferenceEngine
from repro.runtime.kvpool import KVPool
from repro.runtime.request import Request
from repro.runtime.scheduler import Scheduler, VirtualClock

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 host-platform devices")

MAX_LEN = 64
PAGE = 8
CHUNK = 4

LAYOUTS = [("gspmd", dict()), ("tp", dict(t=2)),
           ("pp", dict(t=1, p=2)), ("pp", dict(t=2, p=2))]


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama32-3b").reduced(num_layers=2)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _ragged_requests(cfg, eos_id=None):
    rng = np.random.default_rng(0)
    lens = [(7, 6), (11, 4), (5, 8), (9, 3)]
    return [Request(rid=i,
                    prompt=rng.integers(2, cfg.vocab_size, s).astype(np.int32),
                    max_new_tokens=n, eos_id=eos_id)
            for i, (s, n) in enumerate(lens)]


def _solo_reference(cfg, params, req):
    eng = InferenceEngine(cfg, params, max_len=MAX_LEN, decode_chunk=1)
    out = eng.generate(jnp.asarray(req.prompt)[None, :],
                       max_new_tokens=req.max_new_tokens)
    return np.asarray(out)[0].tolist()


# ---------------------------------------------------------------------------
# paged primitives: update/gather round-trips the contiguous layout
# ---------------------------------------------------------------------------


def _per_layer_write(cache_k, cache_v, k, v, pos, block_table):
    """Reference: the per-layer page write the paged loop used before it
    carried the whole pool — one layer's [P, ps, Hkv, D] pools flattened to
    rows, row ``block_table[b, q // ps] * ps + q % ps`` for position q."""
    P, ps, Hkv, D = cache_k.shape
    B, S = k.shape[:2]
    lp = pos[:, None] + jnp.arange(S)[None, :]
    phys = jnp.take_along_axis(block_table, lp // ps, axis=1)
    rows = (phys * ps + lp % ps).reshape(-1)
    ck = cache_k.reshape(P * ps, Hkv, D).at[rows].set(
        k.reshape(B * S, Hkv, D)).reshape(P, ps, Hkv, D)
    cv = cache_v.reshape(P * ps, Hkv, D).at[rows].set(
        v.reshape(B * S, Hkv, D)).reshape(P, ps, Hkv, D)
    return ck, cv


def _per_layer_gather(pages, block_table):
    """Reference: one layer's logical view, pages [P, ps, Hkv, D] ->
    [B, n*ps, Hkv, D]."""
    B, n = block_table.shape
    _, ps, Hkv, D = pages.shape
    return pages[block_table].reshape(B, n * ps, Hkv, D)


def _block_tables(pool, lanes, n, lengths):
    """Block tables [lanes, n] for sequences of ``lengths`` tokens; a lane
    of length 0 is inactive and keeps every entry on scratch page 0."""
    bt = np.zeros((lanes, n), np.int32)
    for b, length in enumerate(lengths):
        if length:
            row = pool.allocate(b, length)
            bt[b, :len(row)] = row
    return jnp.asarray(bt)


def _live_pages(bt):
    return np.unique(np.asarray(bt)[np.asarray(bt) > 0])


def test_paged_update_gather_matches_contiguous():
    """Writing a chunk through the block table into one layer of the pool,
    then gathering that layer's logical view, reproduces the contiguous
    [B, S, H, D] layout exactly and leaves the other layer untouched."""
    rng = np.random.default_rng(0)
    B, S, H, D, ps, L = 2, 11, 2, 4, 4, 2
    n = -(-S // ps) + 1
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    bt = _block_tables(KVPool(num_pages=2 * n + 1, page_size=ps), B,
                        n, [S, S])
    pool = jnp.zeros((L, 2 * n + 1, ps, H, D), jnp.float32)
    pos = jnp.zeros((B,), jnp.int32)
    ck = layers.paged_layer_write(pool, 1, k, pos, bt)
    cv = layers.paged_layer_write(pool, jnp.int32(1), v, pos, bt)
    got_k = layers.paged_layer_gather(ck, 1, bt)[:, :S]
    got_v = layers.paged_layer_gather(cv, jnp.int32(1), bt)[:, :S]
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(v))
    assert not np.asarray(ck[0]).any() and not np.asarray(cv[0]).any()


@pytest.mark.parametrize("q_len,pos", [(5, [3, 0, 9]), (1, [13, 0, 6])],
                         ids=["chunk", "decode"])
def test_layer_indexed_pair_matches_per_layer_pair(q_len, pos):
    """The layer-indexed write and gather on the whole [L, P, ps, Hkv, D]
    pool agree bitwise with the per-layer pair on that layer's slice, on
    every live page, with lane 1 inactive (aliasing scratch page 0)."""
    rng = np.random.default_rng(1)
    L, P, ps, H, D, n = 3, 12, 4, 2, 4, 5
    lanes = len(pos)
    bt = _block_tables(KVPool(num_pages=P, page_size=ps), lanes, n,
                        [p + q_len if b != 1 else 0
                         for b, p in enumerate(pos)])
    pos = jnp.asarray(pos, jnp.int32)
    pool = jnp.asarray(rng.standard_normal((L, P, ps, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((lanes, q_len, H, D)), jnp.float32)
    live = _live_pages(bt)
    for layer in range(L):
        got = layers.paged_layer_write(pool, jnp.int32(layer), k, pos, bt)
        ref, _ = _per_layer_write(pool[layer], pool[layer], k, k, pos, bt)
        np.testing.assert_array_equal(np.asarray(got[layer][live]),
                                      np.asarray(ref[live]))
        others = np.asarray([i for i in range(L) if i != layer])
        np.testing.assert_array_equal(np.asarray(got[others]),
                                      np.asarray(pool[others]))
        active = np.asarray([0, 2])
        np.testing.assert_array_equal(
            np.asarray(layers.paged_layer_gather(got, layer, bt))[active],
            np.asarray(_per_layer_gather(ref, bt))[active])


def _per_layer_paged_step(cfg, params, cache, tokens, pos, bt):
    """Reference paged pass in the per-layer form: each layer's pool slice
    is cut out, written and gathered by the per-layer pair, and the slices
    are stacked back into a new pool."""
    model = get_model(cfg)
    x = model._embed(params, tokens)
    B, S = tokens.shape
    D = cfg.head_dim
    positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    new_k, new_v = [], []
    for l in range(cfg.num_layers):
        p = {name: w[l] for name, w in params["blocks"].items()}
        xn = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        q = layers.apply_rope((xn @ p["wq"]).reshape(B, S, cfg.num_heads, D),
                              positions, cfg.rope_theta)
        k = layers.apply_rope(
            (xn @ p["wk"]).reshape(B, S, cfg.num_kv_heads, D), positions,
            cfg.rope_theta)
        v = (xn @ p["wv"]).reshape(B, S, cfg.num_kv_heads, D)
        ck, cv = _per_layer_write(cache["k"][l], cache["v"][l], k, v, pos,
                                  bt)
        kg, vg = _per_layer_gather(ck, bt), _per_layer_gather(cv, bt)
        mask = layers.paged_attn_mask(kg.shape[1], pos, S)
        attn = layers.gqa_attention(q, kg, vg, mask)
        x = x + attn.reshape(B, S, cfg.num_heads * D) @ p["wo"]
        x = x + layers.mlp_apply(p, layers.rms_norm(x, p["ln2"],
                                                    cfg.norm_eps),
                                 cfg.activation)
        new_k.append(ck)
        new_v.append(cv)
    logits = model._head(params, x[:, -1:, :])[:, 0]
    return logits, {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}


def test_paged_step_matches_per_layer_reference(setup):
    """``Model.paged_step``, which carries the whole pool through its layer
    loop, gives the per-layer form's logits and pool bitwise: a prefill
    chunk, then a decode round with lane 1 inactive."""
    cfg, params = setup
    model = get_model(cfg)
    rng = np.random.default_rng(2)
    P, n, lanes = 16, MAX_LEN // PAGE, 3
    kv = KVPool(num_pages=P, page_size=PAGE)
    bt = _block_tables(kv, lanes, n, [13, 0, 6])
    cache = model.init_paged_cache(P, PAGE)
    step = jax.jit(model.paged_step)
    ref_step = jax.jit(functools.partial(_per_layer_paged_step, cfg))
    live = _live_pages(bt)
    got_c = ref_c = cache
    for tokens, pos, table in [
            (rng.integers(2, cfg.vocab_size, (1, 12)), [0], bt[:1]),
            (rng.integers(2, cfg.vocab_size, (lanes, 1)), [12, 0, 5], bt)]:
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        got_l, got_c = step(params, got_c, tokens, pos, table)
        ref_l, ref_c = ref_step(params, ref_c, tokens, pos, table)
    active = np.asarray([0, 2])
    np.testing.assert_array_equal(np.asarray(got_l)[active],
                                  np.asarray(ref_l)[active])
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got_c[name])[:, live],
                                      np.asarray(ref_c[name])[:, live])


def test_paged_attn_mask_is_causal_per_sequence():
    m = layers.paged_attn_mask(8, jnp.asarray([3, 0]), 2)   # [B,1,1,S,T]
    m = np.asarray(m)[:, 0, 0]
    # sequence 0: queries at positions 3,4
    assert m[0, 0].tolist() == [True] * 4 + [False] * 4
    assert m[0, 1].tolist() == [True] * 5 + [False] * 3
    # sequence 1: queries at positions 0,1
    assert m[1, 0].tolist() == [True] + [False] * 7
    assert m[1, 1].tolist() == [True] * 2 + [False] * 6


# ---------------------------------------------------------------------------
# acceptance 1: paged == contiguous == solo on ragged traces, 4 layouts
# ---------------------------------------------------------------------------


def test_paged_gspmd_matches_contiguous_and_solo(setup):
    cfg, params = setup
    reqs = _ragged_requests(cfg)
    refs = {r.rid: _solo_reference(cfg, params, r) for r in reqs}
    contiguous = make_backend("gspmd", cfg, params, num_slots=2,
                              max_len=MAX_LEN)
    got_c = Scheduler(contiguous, clock=VirtualClock()).run(
        _ragged_requests(cfg)).tokens_by_rid()
    paged = make_backend("gspmd", cfg, params, num_slots=2, max_len=MAX_LEN,
                         paged=True, page_size=PAGE)
    report = Scheduler(paged, clock=VirtualClock(),
                       chunk_size=CHUNK).run(_ragged_requests(cfg))
    got_p = report.tokens_by_rid()
    for r in reqs:
        assert got_p[r.rid] == refs[r.rid], f"paged diverged on {r.rid}"
        assert got_c[r.rid] == refs[r.rid]
    # chunked prefill really ran: prompt 11 at chunk 4 takes 3 chunk steps
    chunks = [s for s in report.steps if s.phase == "prefill"]
    assert len(chunks) == sum(-(-r.prompt_len // CHUNK) for r in reqs)
    # all pages returned to the pool after the run
    assert paged.pool.stats().used_tokens == 0
    assert paged.pool.free_pages == paged.pool.num_pages - 1


@needs_mesh
@pytest.mark.parametrize("kind,kw", LAYOUTS[1:])
def test_paged_explicit_engines_match_solo(setup, kind, kw):
    cfg, params = setup
    reqs = _ragged_requests(cfg)
    refs = {r.rid: _solo_reference(cfg, params, r) for r in reqs}
    backend = make_backend(kind, cfg, params, num_slots=2, max_len=MAX_LEN,
                           paged=True, page_size=PAGE, **kw)
    got = Scheduler(backend, clock=VirtualClock(),
                    chunk_size=CHUNK).run(_ragged_requests(cfg)).tokens_by_rid()
    for r in reqs:
        assert got[r.rid] == refs[r.rid], \
            f"paged {kind}{kw}: request {r.rid} diverged"


def test_paged_protocol_entrypoint_matches_solo(setup):
    """prefill_into_slots (the non-chunked protocol entry) prefills straight
    into the pages as one maximal chunk — same tokens, no scheduler."""
    cfg, params = setup
    req = _ragged_requests(cfg)[0]
    ref = _solo_reference(cfg, params, req)
    backend = make_backend("gspmd", cfg, params, num_slots=2,
                           max_len=MAX_LEN, paged=True, page_size=PAGE)
    first = backend.prefill_into_slots([req.prompt], [1])
    toks = [int(first[0])]
    pos = np.array([0, req.prompt_len])
    cur = np.array([0, toks[-1]], np.int32)
    for _ in range(req.max_new_tokens - 1):
        nxt = backend.decode_step(cur, pos)
        toks.append(int(nxt[1]))
        cur[1] = nxt[1]
        pos[1] += 1
    assert toks == ref


def test_paged_rejects_unsupported_configs(setup):
    import dataclasses
    cfg, params = setup
    swa_cfg = dataclasses.replace(cfg, sliding_window=32)
    with pytest.raises(ValueError, match="sliding"):
        make_backend("gspmd", swa_cfg, params, num_slots=2, paged=True)
    moe_cfg = get_config("mixtral-8x22b").reduced(num_layers=2)
    with pytest.raises(ValueError, match="dense"):
        make_backend("gspmd", moe_cfg, params, num_slots=2, paged=True)


def test_chunked_prefill_requires_paged_backend(setup):
    cfg, params = setup
    backend = make_backend("gspmd", cfg, params, num_slots=2,
                           max_len=MAX_LEN)
    with pytest.raises(ValueError, match="paged"):
        Scheduler(backend, clock=VirtualClock(), chunk_size=4)


# ---------------------------------------------------------------------------
# acceptance 2: per-chunk + per-step counts == commodel == compiled HLO
# ---------------------------------------------------------------------------


def _hlo_counts(hlo: str):
    return {k: v["count"]
            for k, v in summarize(parse_hlo_collectives(hlo)).items()}


def _count(ops, phase=None):
    counts = {}
    for o in ops:
        if phase in (None, o.phase):
            counts[o.collective] = counts.get(o.collective, 0) + o.count
    return counts


def test_chunked_prefill_ops_totals(setup):
    """Chunked prefill sums to the monolithic prefill: allreduce counts
    scale with n_chunks, total allreduce BYTES are exactly the monolithic
    pass's, and the per-chunk schedule is batch-invariant."""
    cfg, _ = setup
    s_p, chunk = 11, 4
    mono = [o for o in cm.comm_ops_for(cfg, s_p, 1, 2, 1,
                                       gather_mode="allgather")
            if o.phase == "prefill"]
    chunked = cm.chunked_prefill_ops(cfg, s_p, chunk, 2, 1,
                                     gather_mode="allgather")
    n_chunks = -(-s_p // chunk)
    ar_mono = [o for o in mono if o.collective == "allreduce"]
    ar_chunk = [o for o in chunked if o.collective == "allreduce"]
    assert sum(o.count for o in ar_chunk) == \
        n_chunks * sum(o.count for o in ar_mono)
    assert sum(o.total_msg_bytes for o in ar_chunk) == \
        sum(o.total_msg_bytes for o in ar_mono)
    # the head runs per chunk: n_chunks all-gathers instead of 1
    assert sum(o.count for o in chunked if o.collective == "allgather") == \
        n_chunks
    # per-chunk counts don't depend on the chunk length or batch
    for c, batch in [(1, 1), (4, 1), (17, 3)]:
        per = cm.chunked_prefill_ops(cfg, c, c, 2, 1, batch=batch,
                                     gather_mode="allgather")
        assert _count(per) == {"allreduce": 2 * cfg.num_layers + 1,
                               "allgather": 1}


@needs_mesh
def test_paged_tp_chunk_and_decode_hlo_match_commodel(setup):
    """(2,1): compiled HLO of the paged pass at chunk lengths {1, CHUNK}
    and at the decode batch all report the contiguous step's schedule —
    (2L+1) allreduce + 1 logits all-gather — matching chunked_prefill_ops
    and the decode rows of comm_ops_for."""
    cfg, params = setup
    backend = make_backend("tp", cfg, params, num_slots=2, max_len=MAX_LEN,
                           t=2, paged=True, page_size=PAGE)
    want = {"allreduce": 2 * cfg.num_layers + 1, "allgather": 1}
    assert _count(backend.chunk_comm_ops(CHUNK)) == want
    assert _count(backend.decode_comm_ops(), "decode") == want
    for q_len, batch in [(CHUNK, 1), (1, 1), (1, backend.num_slots)]:
        got = _hlo_counts(backend.paged_step_hlo(q_len=q_len, batch=batch))
        assert got == want, (q_len, batch, got)


@needs_mesh
@pytest.mark.parametrize("t,p", [(1, 2), (2, 2)])
def test_paged_pp_stage_hlo_and_measured_chunks(setup, t, p):
    """(1,2)/(2,2): per-stage paged-pass HLO == hybrid_stage_collectives
    (chunk-length-invariant; zero collectives for t=1 stages), and every
    prefill chunk ships exactly the predicted boundary bytes."""
    cfg, params = setup
    backend = make_backend("pp", cfg, params, num_slots=2, max_len=MAX_LEN,
                           t=t, p=p, paged=True, page_size=PAGE)
    for stage in range(p):
        want = cm.hybrid_stage_collectives(cfg, t, p, stage)
        for q_len in (1, CHUNK):
            got = _hlo_counts(backend.stage_paged_hlo(stage, q_len=q_len))
            assert got == want, (stage, q_len, got)

    reqs = _ragged_requests(cfg)
    report = Scheduler(backend, clock=VirtualClock(),
                       chunk_size=CHUNK).run(reqs)
    sizes = [min(CHUNK, r.prompt_len - s)
             for r in sorted(reqs, key=lambda r: r.rid)
             for s in range(0, r.prompt_len, CHUNK)]
    chunks = [s for s in report.steps if s.phase == "prefill"]
    assert len(chunks) == len(sizes)
    for rec, c in zip(chunks, sizes):
        ops = backend.chunk_comm_ops(c)
        send = [o for o in ops if o.collective == "send"][0]
        assert rec.measured_transfers["count"] == send.count == (p - 1) * 2
        assert rec.measured_transfers["bytes"] == send.total_msg_bytes
        assert rec.collective_counts == _count(backend.chunk_comm_ops(CHUNK))
    # decode steps keep the contiguous schedule
    want_dec = _count(backend.decode_comm_ops(), "decode")
    for rec in report.steps:
        if rec.phase == "decode":
            assert rec.collective_counts == want_dec


# ---------------------------------------------------------------------------
# oversubscription: admission is page-aware, never MemoryError
# ---------------------------------------------------------------------------


def test_oversubscribed_pool_queues_instead_of_crashing(setup):
    """A pool with fewer pages than num_slots × worst-case must keep
    requests queued when pages run short (head-of-line, arrival order) and
    still finish everything — the admission gate covers each live request's
    committed decode growth, so mid-decode page extension can never fail."""
    cfg, params = setup
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i,
                    prompt=rng.integers(2, cfg.vocab_size, s).astype(np.int32),
                    max_new_tokens=n)
            for i, (s, n) in enumerate([(30, 6), (25, 5), (28, 4), (20, 6)])]
    refs = {r.rid: _solo_reference(cfg, params, r) for r in reqs}
    # 2 slots would want 2×40 positions; give the pool 9 usable pages (72)
    backend = make_backend("gspmd", cfg, params, num_slots=2, max_len=MAX_LEN,
                           paged=True, page_size=PAGE, num_pages=10)
    report = Scheduler(backend, clock=VirtualClock(),
                       chunk_size=CHUNK).run(reqs)
    got = report.tokens_by_rid()
    for r in reqs:
        assert got[r.rid] == refs[r.rid]
    assert backend.pool.stats().used_tokens == 0


def test_request_larger_than_pool_rejected_at_submit(setup):
    cfg, params = setup
    backend = make_backend("gspmd", cfg, params, num_slots=1, max_len=MAX_LEN,
                           paged=True, page_size=PAGE, num_pages=3)
    sched = Scheduler(backend, clock=VirtualClock(), chunk_size=CHUNK)
    with pytest.raises(ValueError, match="pool capacity"):
        sched.submit(Request(rid=0, prompt=np.arange(2, 30, dtype=np.int32),
                             max_new_tokens=4))


# ---------------------------------------------------------------------------
# acceptance 3: paged Pallas kernel == gather oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ps,hq,hkv,d,n", [
    (16, 8, 2, 64, 4),
    (8, 4, 4, 32, 3),       # MHA
    (32, 4, 1, 64, 2),      # MQA
])
def test_paged_kernel_matches_ref(dtype, ps, hq, hkv, d, n):
    rng = np.random.default_rng(ps + hq + n)
    B, P = 3, n * 3 + 1
    q = jnp.asarray(rng.standard_normal((B, hq, d)), dtype)
    kp = jnp.asarray(rng.standard_normal((P, ps, hkv, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((P, ps, hkv, d)), dtype)
    # each sequence owns a disjoint page run; lengths are ragged
    bt = jnp.asarray([[1 + b * n + j for j in range(n)] for b in range(B)],
                     jnp.int32)
    lengths = jnp.asarray([n * ps, ps + 1, 1], jnp.int32)
    got = paged_decode_attention_pallas(q, kp, vp, bt, lengths,
                                        interpret=True)
    want = paged_decode_attention_ref(q, kp, vp, bt, lengths)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# acceptance 4 (satellite fix): no jitted decode step without active slots
# ---------------------------------------------------------------------------


class _CountingBackend:
    """Transparent proxy that counts decode_step invocations."""

    def __init__(self, inner):
        self._inner = inner
        self.decode_calls = 0

    def decode_step(self, tokens, pos):
        self.decode_calls += 1
        return self._inner.decode_step(tokens, pos)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_no_decode_step_while_only_prefilling(setup):
    """With chunked prefill, iterations that only advance a prompt must not
    burn a fused decode step — the step count equals generated tokens."""
    cfg, params = setup
    req = Request(rid=0, prompt=np.arange(2, 2 + 17, dtype=np.int32),
                  max_new_tokens=3)
    backend = _CountingBackend(make_backend(
        "gspmd", cfg, params, num_slots=2, max_len=MAX_LEN, paged=True,
        page_size=PAGE))
    report = Scheduler(backend, clock=VirtualClock(), chunk_size=4).run([req])
    # 17-token prompt at chunk 4 = 5 chunk-only iterations; 2 decode steps
    # produce tokens 2 and 3 (the first comes from the final chunk)
    assert backend.decode_calls == req.max_new_tokens - 1
    assert len([s for s in report.steps if s.phase == "prefill"]) == 5
    assert report.metrics[0].num_generated == 3


def test_no_decode_step_while_queue_waits(setup):
    """Contiguous mode: a not-yet-arrived queue never triggers the jitted
    step either — the clock just advances to the next arrival."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    r = Request(rid=0, prompt=rng.integers(2, cfg.vocab_size, 6),
                max_new_tokens=2, arrival=50.0)
    backend = _CountingBackend(make_backend(
        "gspmd", cfg, params, num_slots=1, max_len=MAX_LEN))
    clock = VirtualClock()
    Scheduler(backend, clock=clock).run([r])
    assert backend.decode_calls == 1
    assert clock.now() >= 50.0


@needs_mesh
def test_explicit_backends_place_params_and_pools_once(setup):
    """TPBackend places its parameters with ``tp_param_specs`` at
    construction; every PP stage's page pool starts on that stage's mesh,
    t = 1 included (not on the first device)."""
    from jax.sharding import NamedSharding
    from repro.core import parallel_exec as px
    cfg, params = setup
    tp = make_backend("tp", cfg, params, num_slots=2, max_len=MAX_LEN, t=2,
                      paged=True, page_size=PAGE)
    specs = px.tp_param_specs(cfg)
    placed = jax.tree.map(lambda a, sp: a.sharding == NamedSharding(
        tp.mesh, sp), tp.params, specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert all(jax.tree.leaves(placed))
    pp = make_backend("pp", cfg, params, num_slots=2, max_len=MAX_LEN, t=1,
                      p=2, paged=True, page_size=PAGE)
    for s, pool in enumerate(pp.caches):
        for leaf in pool.values():
            assert leaf.sharding.device_set == set(pp.engine.meshes[s]
                                                   .devices.flat)
