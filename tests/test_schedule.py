"""Instruction-queue engines: dynamic pipeline schedules (DESIGN.md §11).

Acceptance, per ISSUE:

1. Closed form: the executed instruction counts and the schedule clock land
   exactly on ``commodel.pp_schedule_stats`` — per-stage StageForward
   counts, boundary hops, SampleTokens, ticks, busy fractions.
2. Bitwise identity: every microbatch's greedy tokens at depth d equal
   depth 1 and solo serving — contiguous AND paged, including under
   scripted preemption / fault schedules (the PR 6 recovery ladder
   survives the dynamic schedule).
3. Traffic identity: each decode round's measured TransferRecords equal
   the PP closed form at the group batch; ``pp_schedule_ops`` composes
   the same totals.
4. The degenerate ``FusedQueue`` preserves the fused backends' behavior
   (StepRecord wall/stage fields, occupancy bookkeeping, proxy safety).
5. The 3-axis (t, c, p) = (2, 2, 2) layout — token identity plus
   predicted == compiled HLO == measured — on the 8-device host mesh
   (``multidevice``: the 2-device CI leg skips it).
"""
import hashlib
import json

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core import commodel as cm
from repro.core.hlo_comm import parse_hlo_collectives, summarize
from repro.models.transformer import get_model
from repro.runtime.backends import make_backend
from repro.runtime.engine import InferenceEngine
from repro.runtime.faults import Fault, FaultInjector
from repro.runtime.request import Request
from repro.runtime.schedule import FusedQueue, make_queue
from repro.runtime.scheduler import Scheduler, VirtualClock, serve

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 host-platform devices")

MAX_LEN = 64
GROUP = 2          # slots per microbatch group (bench OCC_GROUP)
NEW_TOKENS = 5     # per request → NEW_TOKENS - 1 decode rounds


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama32-3b").reduced(num_layers=2)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, n, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, cfg.vocab_size, 8).astype(np.int32)
            for _ in range(n)]


def _requests(cfg, n):
    return [Request(rid=i, prompt=p, max_new_tokens=NEW_TOKENS)
            for i, p in enumerate(_prompts(cfg, n))]


def _pp(cfg, params, p, d, **kw):
    return make_backend("pp", cfg, params, num_slots=GROUP * d,
                        max_len=MAX_LEN, t=1, p=p, inflight=d, **kw)


def _count(ops):
    counts = {}
    for o in ops:
        counts[o.collective] = counts.get(o.collective, 0) + o.count
    return counts


def _hlo_counts(hlo):
    return {k: v["count"] for k, v in summarize(
        parse_hlo_collectives(hlo)).items()}


# ---------------------------------------------------------------------------
# closed form: pp_schedule_stats pins the executed program
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p,depth,rounds", [(2, 1, 4), (2, 2, 4), (4, 3, 5),
                                            (4, 4, 5), (8, 2, 3)])
def test_pp_schedule_stats_closed_form(p, depth, rounds):
    st = cm.pp_schedule_stats(p, depth, rounds)
    assert st.ticks == rounds * max(p, depth) + min(p, depth) - 1
    assert st.stage_forwards == (depth * rounds,) * p
    assert st.boundary_sends == (p - 1) * 2 * depth * rounds
    assert st.samples == depth * rounds
    assert st.busy_fraction == depth * rounds / st.ticks
    # depth capped by p never beats a fully busy pipeline
    assert st.busy_fraction <= 1.0


def test_pp_schedule_stats_validates():
    with pytest.raises(ValueError):
        cm.pp_schedule_stats(0, 1, 1)
    with pytest.raises(ValueError):
        cm.pp_schedule_stats(2, -1, 1)
    assert cm.pp_schedule_stats(2, 0, 4).ticks == 0
    assert cm.pp_schedule_ops(get_config("llama32-3b"), 0, 4, 2) == []
    assert cm.pp_schedule_ops(get_config("llama32-3b"), 2, 4, 1) == []


@pytest.mark.parametrize("d", [1, 2])
def test_executed_instructions_match_closed_form(setup, d):
    """One admission wave at depth d: the queue's instruction counts and
    schedule clock are exactly pp_schedule_stats(p, d, rounds)."""
    cfg, params = setup
    p, rounds = 2, NEW_TOKENS - 1
    backend = _pp(cfg, params, p, d)
    sched = Scheduler(backend, clock=VirtualClock())
    sched.run(_requests(cfg, GROUP * d))
    q = sched._queue
    st = cm.pp_schedule_stats(p, d, rounds)
    assert q.ticks == st.ticks
    assert tuple(q.busy) == st.stage_forwards
    assert q.idle == [st.ticks - b for b in st.stage_forwards]
    for s in range(p):
        assert q.counts["StageForward", s] == st.stage_forwards[s]
    n_send = sum(n for (k, _), n in q.counts.items() if k == "BoundarySend")
    n_recv = sum(n for (k, _), n in q.counts.items() if k == "BoundaryRecv")
    assert n_send == n_recv == st.boundary_sends // 2
    assert q.counts["SampleToken", None] == st.samples
    # one PrefillChunk per admitted request, noted before its decode
    assert q.counts["PrefillChunk", None] == GROUP * d


def test_occupancy_report_matches_closed_form(setup):
    """ServingReport.occupancy() reproduces the closed form through the
    StepRecord deltas — the quantity the pp-occupancy bench series gates."""
    cfg, params = setup
    p, rounds, waves = 2, NEW_TOKENS - 1, 2
    reports = {}
    for d in (1, 2):
        backend = _pp(cfg, params, p, d)
        # R = GROUP·p requests: depth 1 runs two admission waves, depth 2 one
        reports[d] = serve(backend, _requests(cfg, GROUP * p),
                           clock=VirtualClock())
    occ1 = reports[1].occupancy()
    occ2 = reports[2].occupancy()
    st1 = cm.pp_schedule_stats(p, 1, rounds)
    st2 = cm.pp_schedule_stats(p, 2, rounds)
    assert occ1["ticks"] == waves * st1.ticks
    assert occ2["ticks"] == st2.ticks
    assert occ1["decode_tokens"] == occ2["decode_tokens"] \
        == GROUP * p * rounds
    assert occ1["stage_busy_fraction"] == [st1.busy_fraction] * p
    assert occ2["stage_busy_fraction"] == [st2.busy_fraction] * p
    # the tentpole ratio: depth p fills the bubble
    ratio = occ2["tokens_per_tick"] / occ1["tokens_per_tick"]
    assert ratio == pytest.approx(waves * st1.ticks / st2.ticks)
    assert ratio >= 1.5
    assert occ2["busy_fraction_mean"] >= 0.8


# ---------------------------------------------------------------------------
# bitwise identity: depth d == depth 1 == solo, contiguous and paged
# ---------------------------------------------------------------------------


def _solo_reference(cfg, params, req):
    eng = InferenceEngine(cfg, params, max_len=MAX_LEN, decode_chunk=1)
    out = eng.generate(np.asarray(req.prompt)[None, :],
                       max_new_tokens=req.max_new_tokens)
    return np.asarray(out)[0].tolist()


@pytest.mark.parametrize("paged", [False, True])
def test_depth_identity_contiguous_and_paged(setup, paged):
    cfg, params = setup
    p = 2
    reqs = _requests(cfg, GROUP * p)
    refs = {r.rid: _solo_reference(cfg, params, r) for r in reqs[:2]}
    got = {}
    for d in (1, 2):
        kw = dict(paged=True, page_size=8, num_pages=64) if paged else {}
        backend = _pp(cfg, params, p, d, **kw)
        got[d] = serve(backend, _requests(cfg, GROUP * p),
                       clock=VirtualClock()).tokens_by_rid()
    assert got[1] == got[2]
    for rid, ref in refs.items():
        assert got[2][rid] == ref, f"rid {rid} diverged from solo serving"


@needs_mesh
def test_depth_identity_pp4(setup):
    """pp4 at depth 4: one wave of 4 groups, tokens == depth 1 (which runs
    4 waves), ticks == closed form at both depths."""
    cfg, params = setup
    p, rounds = 4, NEW_TOKENS - 1
    got, ticks = {}, {}
    for d in (1, 4):
        sched = Scheduler(_pp(cfg, params, p, d), clock=VirtualClock())
        rep = sched.run(_requests(cfg, GROUP * p))
        got[d] = rep.tokens_by_rid()
        ticks[d] = rep.occupancy()["ticks"]
    assert got[1] == got[4]
    assert ticks[1] == p * cm.pp_schedule_stats(p, 1, rounds).ticks
    assert ticks[4] == cm.pp_schedule_stats(p, 4, rounds).ticks
    # the ISSUE's headline: ≥ 2× tokens/tick at depth p
    assert ticks[1] / ticks[4] >= 2.0


# ---------------------------------------------------------------------------
# recovery ladder under the dynamic schedule
# ---------------------------------------------------------------------------


def test_transient_faults_identical_at_depth2(setup):
    cfg, params = setup
    ref = serve(_pp(cfg, params, 2, 1), _requests(cfg, 4),
                clock=VirtualClock()).tokens_by_rid()
    inj = FaultInjector.scripted({
        ("decode", 2): Fault("decode", "transient"),
        ("pp_transfer", 4): Fault("pp_transfer", "transient")})
    rep = Scheduler(_pp(cfg, params, 2, 2), clock=VirtualClock(),
                    faults=inj, retry_backoff=0.1).run(_requests(cfg, 4))
    assert rep.tokens_by_rid() == ref
    assert rep.retries >= 2


def test_pool_pressure_preemption_identical_at_depth2(setup):
    """A page pool that cannot hold both groups forces real mid-schedule
    preemption: victims come only from groups with no issued work, the
    preempted requests recompute, and the streams stay bitwise identical."""
    cfg, params = setup
    ref = serve(_pp(cfg, params, 2, 1), _requests(cfg, 4),
                clock=VirtualClock()).tokens_by_rid()
    backend = _pp(cfg, params, 2, 2, paged=True, page_size=8, num_pages=6)
    rep = Scheduler(backend, clock=VirtualClock(),
                    admission="optimistic").run(_requests(cfg, 4))
    assert rep.preemptions > 0
    assert rep.tokens_by_rid() == ref
    assert backend.pool.stats().used_tokens == 0


def test_scripted_pool_fault_identical_at_depth2(setup):
    cfg, params = setup
    ref = serve(_pp(cfg, params, 2, 1), _requests(cfg, 4),
                clock=VirtualClock()).tokens_by_rid()
    inj = FaultInjector.scripted({("pool", 3): Fault("pool", "oom")})
    rep = Scheduler(_pp(cfg, params, 2, 2, paged=True, page_size=8,
                        num_pages=64),
                    clock=VirtualClock(), faults=inj,
                    admission="optimistic").run(_requests(cfg, 4))
    assert rep.preemptions == 1
    assert rep.tokens_by_rid() == ref


def test_cancel_mid_schedule_drains_only_that_round(setup):
    """Cancelling a request mid-schedule syncs the queue (its in-flight
    instructions drain) and the survivors' streams are untouched."""
    cfg, params = setup
    ref = serve(_pp(cfg, params, 2, 1), _requests(cfg, 4),
                clock=VirtualClock()).tokens_by_rid()
    sched = Scheduler(_pp(cfg, params, 2, 2), clock=VirtualClock())
    for r in _requests(cfg, 4):
        sched.submit(r)
    for _ in range(4):
        sched.step()
    assert sched.cancel(2)
    got = sched.run().tokens_by_rid()
    for rid in (0, 1, 3):
        assert got[rid] == ref[rid]
    assert len(got[2]) < len(ref[2])
    # the cancel counted a Sync barrier before touching slot state
    assert sched._queue.counts["Sync", None] > 0


# ---------------------------------------------------------------------------
# traffic: measured per-round transfers == closed form == pp_schedule_ops
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d", [1, 2])
def test_round_transfers_match_closed_form(setup, d):
    cfg, params = setup
    p, rounds = 2, NEW_TOKENS - 1
    backend = _pp(cfg, params, p, d)
    rep = serve(backend, _requests(cfg, GROUP * d), clock=VirtualClock())
    send = [o for o in backend.decode_comm_ops(batch=GROUP)
            if o.collective == "send"]
    want_count = sum(o.count for o in send)
    want_bytes = sum(o.total_msg_bytes for o in send)
    dec = [r for r in rep.steps if r.phase == "decode"]
    assert len(dec) == d * rounds
    for r in dec:
        assert r.measured_transfers["count"] == want_count
        assert r.measured_transfers["bytes"] == want_bytes
    # pp_schedule_ops composes the identical totals (host f32: b=4)
    ops = cm.pp_schedule_ops(cfg, d, rounds, p, t=1, b=4, group=GROUP)
    s_ops = [o for o in ops if o.collective == "send"]
    assert sum(o.count for o in s_ops) == len(dec) * want_count
    assert sum(o.total_msg_bytes for o in s_ops) == len(dec) * want_bytes


def test_token_checksum_depth_invariant(setup):
    """The bench's token_checksum construction is depth-invariant — the
    same hash the pp-occupancy gate compares across depths."""
    cfg, params = setup
    sums = set()
    for d in (1, 2):
        got = serve(_pp(cfg, params, 2, d), _requests(cfg, 4),
                    clock=VirtualClock()).tokens_by_rid()
        sums.add(hashlib.sha256(
            json.dumps(got, sort_keys=True).encode()).hexdigest())
    assert len(sums) == 1


# ---------------------------------------------------------------------------
# degenerate FusedQueue + StepRecord surface
# ---------------------------------------------------------------------------


def test_fused_queue_on_gspmd_backend(setup):
    cfg, params = setup
    backend = make_backend("gspmd", cfg, params, num_slots=2,
                           max_len=MAX_LEN)
    q = make_queue(backend)
    assert isinstance(q, FusedQueue)
    assert (q.p, q.depth, q.group_size) == (1, 1, 2)
    assert q.busy_groups() == set() and q.pending_groups() == set()
    q.begin_round(0, np.zeros(2, np.int32), np.zeros(2, np.int32), 2)
    assert q.pending_groups() == {0} and q.busy_groups() == set()
    with pytest.raises(RuntimeError):
        q.begin_round(0, np.zeros(2, np.int32), np.zeros(2, np.int32), 2)


def test_step_records_carry_wall_and_stage_fields(setup):
    cfg, params = setup
    backend = _pp(cfg, params, 2, 2, paged=True, page_size=8, num_pages=64)
    rep = Scheduler(backend, clock=VirtualClock(),
                    chunk_size=4).run(_requests(cfg, 4))
    dec = [r for r in rep.steps if r.phase == "decode"]
    pre = [r for r in rep.steps if r.phase == "prefill"]
    assert dec and pre
    for r in dec:
        assert r.wall_s > 0.0
        assert len(r.stage_busy) == len(r.stage_idle) == 2
        assert sum(r.stage_busy) > 0
    for r in pre:
        assert r.wall_s > 0.0
        assert r.stage_busy is None and r.stage_idle is None
    # fused backends keep the degenerate [1]/[0] stage shape
    rep = serve(make_backend("gspmd", cfg, params, num_slots=2,
                             max_len=MAX_LEN),
                _requests(cfg, 2), clock=VirtualClock())
    for r in rep.steps:
        if r.phase == "decode":
            assert r.stage_busy == [1] and r.stage_idle == [0]


def test_make_backend_rejects_bad_inflight(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="inflight"):
        make_backend("gspmd", cfg, params, num_slots=2, max_len=MAX_LEN,
                     inflight=2)
    with pytest.raises(ValueError, match="divide"):
        make_backend("pp", cfg, params, num_slots=3, max_len=MAX_LEN,
                     t=1, p=2, inflight=2)


# ---------------------------------------------------------------------------
# occupancy in the analytical stack (slo + planner)
# ---------------------------------------------------------------------------


def test_predict_slo_occupancy_term(setup):
    cfg, _ = setup
    from repro.core.slo import predict_goodput, predict_slo
    base = predict_slo(cfg, 8, 4, t=1, p=4)
    same = predict_slo(cfg, 8, 4, t=1, p=4, inflight=1)
    assert base.e2e == same.e2e and base.tpot == same.tpot
    assert base.occupancy == 0.25
    assert base.breakdown["tpot_effective"] == base.tpot
    deep = predict_slo(cfg, 8, 4, t=1, p=4, inflight=4)
    assert deep.occupancy == 1.0
    assert deep.breakdown["tpot_effective"] == deep.tpot / 4
    assert deep.e2e < base.e2e
    # depth beyond p saturates; p=1 has no bubble to fill
    assert predict_slo(cfg, 8, 4, t=1, p=4, inflight=8).occupancy == 1.0
    assert predict_slo(cfg, 8, 4, t=2, p=1, inflight=4).occupancy == 1.0
    gp1 = predict_goodput(cfg, 8, 8, num_slots=4, capacity_tokens=512)
    gp4 = predict_goodput(cfg, 8, 8, num_slots=4, capacity_tokens=512,
                          t=1, p=4, inflight=4)
    assert gp1.breakdown["pp_occupancy"] == 1.0
    assert gp4.breakdown["pp_occupancy"] == 1.0
    assert gp4.goodput_tok_s > predict_goodput(
        cfg, 8, 8, num_slots=4, capacity_tokens=512,
        t=1, p=4).goodput_tok_s


def test_planner_ranks_with_occupancy(setup):
    cfg, _ = setup
    from repro.core.planner import plan
    base = plan(cfg, 4, 64, 16, objective="tpot")
    deep = plan(cfg, 4, 64, 16, objective="tpot", inflight=4)
    for c in base:
        assert c.occupancy == (1.0 if c.pipeline_parallel == 1
                               else 1 / c.pipeline_parallel)
    # filling the bubble can only help PP layouts: the best pp>1
    # candidate's score improves, pure-TP scores are unchanged
    b_by = {(c.tensor_parallel, c.context_parallel, c.pipeline_parallel): c
            for c in base}
    d_by = {(c.tensor_parallel, c.context_parallel, c.pipeline_parallel): c
            for c in deep}
    for key, c in d_by.items():
        if key[2] == 1:
            assert c.score == b_by[key].score
        else:
            assert c.score < b_by[key].score
            assert c.occupancy == min(4, key[2]) / key[2]


# ---------------------------------------------------------------------------
# the 3-axis point: (t, c, p) = (2, 2, 2) with the dynamic schedule
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
def test_three_axis_dynamic_schedule(setup):
    """(2,2,2) on the 8-device mesh at depth 2: tokens bitwise equal the
    (1,1,2) depth-1 stream, ticks/busy on the closed form, per-stage
    decode HLO collective-free over cp with the hybrid TP rows, and the
    measured per-round boundary bytes at the [group, h/t] shard."""
    cfg, params = setup
    t, c, p = 2, 2, 2
    ref = serve(_pp(cfg, params, p, 1), _requests(cfg, GROUP * p),
                clock=VirtualClock()).tokens_by_rid()
    backend = make_backend("pp", cfg, params, num_slots=GROUP * 2,
                           max_len=MAX_LEN, t=t, c=c, p=p, inflight=2)
    # predicted == compiled: per-stage decode modules show the hybrid
    # TP schedule (cp replicates decode, adding no collectives)
    for s in range(p):
        assert _hlo_counts(backend.stage_decode_hlo(s)) == \
            cm.hybrid_stage_collectives(cfg, t, p, s, c=c, phase="decode"), s
    backend.drain_transfers()
    rep = serve(backend, _requests(cfg, GROUP * p), clock=VirtualClock())
    assert rep.tokens_by_rid() == ref
    occ = rep.occupancy()
    st = cm.pp_schedule_stats(p, 2, NEW_TOKENS - 1)
    assert occ["ticks"] == st.ticks
    assert occ["stage_busy_fraction"] == [st.busy_fraction] * p
    # predicted == measured: every round's boundary hops at batch=GROUP
    send = [o for o in backend.decode_comm_ops(batch=GROUP)
            if o.collective == "send"]
    dec = [r for r in rep.steps if r.phase == "decode"]
    for r in dec:
        assert r.measured_transfers["count"] == sum(o.count for o in send)
        assert r.measured_transfers["bytes"] == \
            sum(o.total_msg_bytes for o in send)
