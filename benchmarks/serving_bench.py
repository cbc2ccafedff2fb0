"""Serving benchmark: continuous batching under Poisson traffic (paper §V-C).

A CPU tool: its child process runs on ``JAX_PLATFORMS=cpu`` with four
forced host devices and ``reduced()`` configs, so every time it reports is
a CPU wall time, never a device metric.  It stays that until a benchmark
measured on the chip replaces it; ``chip_smoke.py`` is what runs on the
chip today.

Drives the continuous-batching scheduler (runtime/scheduler.py) over each
DecodeBackend with mixed-length request traces at increasing arrival rates,
producing the throughput-vs-latency curves the paper's SLO section draws from
measurement — measured TTFT / TPOT / E2E sit next to the analytical
``core.slo.predict_slo`` prediction for the same layout, so the two sides of
the paper's methodology (measure + model) face each other at request level.

Seven series (4-device host-platform mesh):

  short       gspmd / tp2 / pp2, contiguous slots, prompts 8–48 at three
              arrival rates — the original throughput-vs-latency sweep
  longctx     prompts spanning 16–512 (the regime where a contiguous
              ``max_len`` slot pool wastes most of its memory): contiguous
              vs ``paged=True`` + chunked prefill on the same trace — the
              paged-vs-contiguous throughput series (DESIGN.md §8)
  cp-longctx  the same long-context trace through the explicit
              single-stage engine at cp ∈ {1, 2, 4} (DESIGN.md §9):
              per-prompt-length mean TTFT (``ttft_by_prompt_len``) shows
              where sequence-sharded prefill starts paying for its ring
  overload    an EOS-heavy closed trace (``eos_prob``) on an oversubscribed
              page pool, conservative vs optimistic admission (DESIGN.md
              §10): optimistic packs more requests per fused decode step
              and pays with preemption-by-recompute — check_baselines
              gates ``tokens_per_decode_step`` (optimistic ≥ conservative,
              compared within the dry-run file: it is trace-dependent, so
              it is not diffed against the full-series baseline) and the
              recompute collective counts; the run completing at all is
              the zero-MemoryError-escapes assertion
  prefix-cache  a template-heavy closed trace (``make_template_trace``,
              DESIGN.md §13) served twice through tp2 paged + chunked
              prefill: once cold, once with the cross-request prefix
              index live — ``check_baselines.check_prefix_cache`` gates
              bitwise token identity between the two (checksum), executed
              prefill chunks/counts == the per-request suffix arithmetic
              (``commodel.prefix_cache_ops``'s executed column), hit TTFT
              strictly below the cold run's on the same rids, and a
              zero-leak pool drain once the index is cleared
  disagg-mixed  the §14 acceptance bench: one seeded chat+summarize trace
              served three ways — the chat subset alone (the decode
              pool's gate baseline), the full mix colocated (long
              prefill chunks steal decode steps: head-of-line blocking),
              and the full mix through ``DisaggScheduler`` (longs
              prefill in a 1-slot prefill pool sharing the decode
              pool's KVPool, finished pages ship on the modeled
              interconnect).  ``check_baselines.check_disagg`` gates
              bitwise chat-stream identity across all three, measured
              handoff bytes == the ``kv_handoff_ops`` closed form, a
              zero-leak drain, the §14 planner's decision rule, and (on
              the full series) decode-pool chat p99 TPOT within 1.10×
              of the baseline while colocated degrades ≥ 1.5×
  pp-occupancy  the dynamic-schedule payoff curve (DESIGN.md §11): the SAME
              closed request set through pp2/pp4 at in-flight depth
              d ∈ 1..p (``num_slots = 2·d`` so depth adds concurrent
              groups, never shrinks them).  Every quantity gated here is
              on the deterministic schedule clock — decode ticks, tokens
              per tick and per-stage busy fractions land EXACTLY on
              ``commodel.pp_schedule_stats`` (single-process hosts cannot
              overlap stages in wall time, so wall tokens/s is reported
              but not gated), per-round boundary bytes land exactly on the
              PP closed form, and the token checksum is depth-invariant —
              the bitwise-identity acceptance across schedules

Every record carries the *predicted* per-step decode collective counts (and,
for paged runs, the per-chunk prefill counts; for CP runs, the per-prefill
counts with the ring rows) from ``commodel`` — these are deterministic and
machine-independent, so CI's bench-regression gate
(`benchmarks/check_baselines.py`) can diff them against the checked-in
``BENCH_serve.json`` without chasing timing noise.

Emits ``BENCH_serve.json`` at the repo root.  Runs in a subprocess so the
device flag stays contained.  ``--dry-run`` serves one tiny closed trace per
backend (including a paged one) and writes ``results/BENCH_serve.dryrun.json``
for the CI artifact + drift gate instead of the full series.
"""
import json
import os
import subprocess
import sys

ARCH = "llama32-3b"
REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
OUT_PATH = os.path.join(REPO, "BENCH_serve.json")
DRY_PATH = os.path.join(REPO, "results", "BENCH_serve.dryrun.json")

N_REQUESTS = 24
NUM_SLOTS = 4
DRY_REQUESTS = 4
DRY_SLOTS = 2
MAX_LEN = 96
RATES = [2.0, 8.0, 0.0]          # req/s; 0 = closed batch (all at t=0)
PROMPT_LENS = (8, 48)
DECODE_LENS = (4, 24)

# long-context mixed trace: prompts up to 512 tokens (paged vs contiguous)
LONG_PROMPT_LENS = (16, 512)
LONG_DECODE_LENS = (4, 16)
LONG_MAX_LEN = 544
LONG_REQUESTS = 8
LONG_QUANTUM = 32
CHUNK_SIZE = 64
PAGE_SIZE = 16

# overload series: EOS-heavy mix on a pool that cannot hold every slot's
# worst case at once (DESIGN.md §10)
OV_REQUESTS = 16
OV_PROMPT_LENS = (8, 32)
OV_DECODE_LENS = (6, 20)
OV_MAX_LEN = 64
OV_EOS_PROB = 0.3

# prefix-cache series: template-heavy trace on tp2 paged + chunked with
# the cross-request prefix index (DESIGN.md §13).  Two-page templates so
# every hit adopts full blocks; suffixes stay under one chunk.
PC_REQUESTS = 16
PC_TEMPLATE_PAGES = 2
PC_SUFFIX_LENS = (4, 12)
PC_DECODE_LENS = (4, 8)
PC_MAX_LEN = 96

# disagg-mixed series (DESIGN.md §14): chat + summarize traffic, three
# ways — the chat subset alone (the decode pool's gate baseline), the
# full mix colocated (long prefill chunks steal decode steps: the
# head-of-line blocking the paper's mixed traces measure), and the full
# mix through DisaggScheduler (longs prefill in a 1-slot prefill pool
# sharing the decode pool's KVPool; finished pages ship on the modeled
# interconnect and chat TPOT is measured on the decode pool's clock).
DM_CHAT_REQUESTS = 18
DM_LONG_REQUESTS = 4
DM_CHAT_PROMPTS = (8, 24)        # strictly under DM_ROUTE: never routed
DM_CHAT_DECODE = (8, 16)
DM_LONG_PROMPTS = (192, 320)
DM_LONG_DECODE = (4, 8)
DM_CHAT_RATE = 4.0
DM_LONG_RATE = 1.0
DM_ROUTE = 48
DM_MAX_LEN = 352
DM_PAGES = 128
DM_SLOTS = 4

# pp-occupancy series: dynamic-schedule depth sweep (DESIGN.md §11).  A
# request group is OCC_GROUP slots; depth d runs d groups in flight on
# num_slots = OCC_GROUP·d, and every depth serves the same seeded
# OCC_GROUP·p-request closed set so tokens are comparable bitwise.
OCC_GROUP = 2
OCC_PROMPT_LEN = 8
OCC_NEW_TOKENS = 6


def _measure(dry_run: bool = False):
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.slo import predict_slo
    from repro.models.transformer import get_model
    from repro.runtime.backends import make_backend
    from repro.runtime.request import Request, make_poisson_trace
    from repro.runtime.scheduler import Scheduler, step_collective_counts

    cfg = get_config(ARCH).reduced(num_layers=4)
    params = get_model(cfg).init(jax.random.PRNGKey(0))

    def _count(ops):
        counts = {}
        for o in ops:
            counts[o.collective] = counts.get(o.collective, 0) + o.count
        return counts

    def chunk_counts(backend, chunk):
        return _count(backend.chunk_comm_ops(chunk))

    def run_series(series, kind, name, t, p, paged, chunk, num_slots,
                   max_len, traces, warm_lens, rates, sp_mean, sd_mean):
        backend = make_backend(kind, cfg, params, num_slots=num_slots,
                               max_len=max_len, t=t, p=p, paged=paged,
                               page_size=PAGE_SIZE)
        sched = lambda: Scheduler(backend,
                                  chunk_size=chunk if paged else None)
        # warm the compile caches off the clock: one 2-token request per
        # distinct bucketed prompt length, plus the decode step itself
        wrng = np.random.default_rng(1)
        warm = [Request(rid=10_000 + j,
                        prompt=wrng.integers(2, cfg.vocab_size, s),
                        max_new_tokens=2)
                for j, s in enumerate(sorted(warm_lens))]
        sched().run(warm)
        # analytical counterpart at THIS series' mean request shape
        pred = predict_slo(cfg, sp_mean, sd_mean, t=t, p=p)
        out = []
        for rate in rates:
            report = sched().run(traces[rate])
            s = report.summary()
            out.append({
                "series": series, "arch": cfg.name, "backend": name,
                "tp": t, "cp": 1, "pp": p, "paged": paged,
                "chunk_size": chunk if paged else None,
                "inflight": 1, "num_slots": num_slots, "rate_req_s": rate,
                **s,
                "queue_delay_mean_s": float(
                    sum(m.queue_delay for m in report.metrics)
                    / len(report.metrics)),
                "decode_steps": len([r for r in report.steps
                                     if r.phase == "decode"]),
                "prefill_chunks": len([r for r in report.steps
                                       if r.phase == "prefill"]),
                "decode_collective_counts":
                    step_collective_counts(backend, 1),
                "prefill_chunk_counts":
                    chunk_counts(backend, chunk) if paged else None,
                "predicted_ttft_s": pred.ttft,
                "predicted_tpot_s": pred.tpot,
                "predicted_e2e_s": pred.e2e,
            })
        return out

    n_requests = DRY_REQUESTS if dry_run else N_REQUESTS
    num_slots = DRY_SLOTS if dry_run else NUM_SLOTS
    rates = [0.0] if dry_run else RATES

    results = []
    # -- short series: gspmd vs tp2 vs pp2 (contiguous, as before) + a
    #    paged gspmd point so paged-vs-contiguous exists at every scale
    short_backends = [("gspmd", "gspmd", 1, 1, False),
                      ("tp", "tp2", 2, 1, False),
                      ("pp", "pp2", 1, 2, False),
                      ("gspmd", "gspmd-paged", 1, 1, True)]
    traces = {rate: make_poisson_trace(
        n_requests, rate, cfg.vocab_size, prompt_lens=PROMPT_LENS,
        decode_lens=DECODE_LENS, seed=7, quantum=8) for rate in rates}
    warm_lens = {r.prompt_len for t in traces.values() for r in t}
    for kind, name, t, p, paged in short_backends:
        results += run_series("short", kind, name, t, p, paged,
                              8 if dry_run else CHUNK_SIZE // 4, num_slots,
                              MAX_LEN, traces, warm_lens, rates,
                              sum(PROMPT_LENS) // 2, sum(DECODE_LENS) // 2)

    # -- long-context series: prompts 16–512, paged vs contiguous on the
    #    same closed trace (arrival rate stresses nothing new here)
    long_n = 3 if dry_run else LONG_REQUESTS
    long_lens = (16, 96) if dry_run else LONG_PROMPT_LENS
    long_max = 128 if dry_run else LONG_MAX_LEN
    ltraces = {0.0: make_poisson_trace(
        long_n, 0.0, cfg.vocab_size, prompt_lens=long_lens,
        decode_lens=LONG_DECODE_LENS, seed=11, quantum=LONG_QUANTUM)}
    lwarm = {r.prompt_len for t in ltraces.values() for r in t}
    for name, paged in [("gspmd", False), ("gspmd-paged", True)]:
        results += run_series("longctx", "gspmd", name, 1, 1, paged,
                              16 if dry_run else CHUNK_SIZE, num_slots,
                              long_max, ltraces, lwarm, [0.0],
                              sum(long_lens) // 2,
                              sum(LONG_DECODE_LENS) // 2)

    # -- CP prefill series: the same long-context closed trace through the
    #    explicit single-stage engine at cp ∈ {1, 2, 4} — TTFT vs prompt
    #    length is the payoff curve of sequence-sharded prefill
    #    (DESIGN.md §9).  TPBackend at t=1, c=1 is the 1-device explicit
    #    engine: the same code path as the c>1 points, so the TTFT deltas
    #    are the ring's, not an engine swap's.
    from repro.runtime.backends import TPBackend

    for cdeg in ([1, 2] if dry_run else [1, 2, 4]):
        backend = TPBackend(cfg, params, num_slots=num_slots,
                            max_len=long_max, t=1, c=cdeg)
        sched = lambda: Scheduler(backend)
        wrng = np.random.default_rng(1)
        sched().run([Request(rid=10_000 + j,
                             prompt=wrng.integers(2, cfg.vocab_size, s),
                             max_new_tokens=2)
                     for j, s in enumerate(sorted(lwarm))])
        report = sched().run(ltraces[0.0])
        by_len = {}
        for m in report.metrics:
            by_len.setdefault(m.prompt_len, []).append(m.ttft)
        pred = predict_slo(cfg, sum(long_lens) // 2,
                           sum(LONG_DECODE_LENS) // 2, t=1, c=cdeg)
        s = report.summary()
        results.append({
            "series": "cp-longctx", "arch": cfg.name,
            "backend": f"cp{cdeg}", "tp": 1, "cp": cdeg, "pp": 1,
            "paged": False, "chunk_size": None, "inflight": 1,
            "num_slots": num_slots, "rate_req_s": 0.0, **s,
            "ttft_by_prompt_len_s": {
                str(k): float(np.mean(v))
                for k, v in sorted(by_len.items())},
            "decode_collective_counts":
                step_collective_counts(backend, 1),
            "prefill_collective_counts":
                _count(backend.prefill_comm_ops(64)),
            "predicted_ttft_s": pred.ttft,
            "predicted_tpot_s": pred.tpot,
            "predicted_e2e_s": pred.e2e,
        })
    # -- pp-occupancy series: the dynamic instruction-queue schedule
    #    (DESIGN.md §11) at in-flight depth 1..p.  One request group is
    #    OCC_GROUP slots; depth d serves d groups concurrently
    #    (num_slots = OCC_GROUP·d), and every depth serves the IDENTICAL
    #    seeded request set, so tokens must be bitwise depth-invariant.
    #    All gated quantities are schedule-clock (tick) exact:
    #    check_baselines diffs them against commodel.pp_schedule_stats.
    import hashlib

    from repro.core.commodel import pp_schedule_stats

    occ_m = 4 if dry_run else OCC_NEW_TOKENS       # tokens per request
    occ_rounds = occ_m - 1                         # decode rounds after prefill
    for p in ([2] if dry_run else [2, 4]):
        n_req = OCC_GROUP * p
        prng = np.random.default_rng(23)
        prompts = [prng.integers(2, cfg.vocab_size, OCC_PROMPT_LEN)
                   .astype(np.int32) for _ in range(n_req)]
        checksums = {}
        for d in range(1, p + 1):
            slots = OCC_GROUP * d
            backend = make_backend("pp", cfg, params, num_slots=slots,
                                   max_len=MAX_LEN, t=1, p=p, inflight=d)
            sched = lambda: Scheduler(backend)
            wrng = np.random.default_rng(1)
            sched().run([Request(rid=10_000,
                                 prompt=wrng.integers(2, cfg.vocab_size,
                                                      OCC_PROMPT_LEN),
                                 max_new_tokens=2)])
            report = sched().run([
                Request(rid=i, prompt=prompts[i], max_new_tokens=occ_m)
                for i in range(n_req)])
            s = report.summary()
            occ = report.occupancy()
            toks = report.tokens_by_rid()
            checksum = hashlib.sha256(
                json.dumps(toks, sort_keys=True).encode()).hexdigest()
            checksums[d] = checksum
            # the scheduler admits in waves of `slots` requests (admission
            # syncs the queue), so predicted ticks compose per wave
            pred_ticks, pred_busy_rounds, left = 0, 0, n_req
            while left > 0:
                wave = min(left, slots)
                left -= wave
                st = pp_schedule_stats(p, wave // OCC_GROUP, occ_rounds)
                pred_ticks += st.ticks
                pred_busy_rounds += st.stage_forwards[0]
            send = [o for o in backend.decode_comm_ops(batch=OCC_GROUP)
                    if o.collective == "send"]
            dec = [r for r in report.steps if r.phase == "decode"]
            results.append({
                "series": "pp-occupancy", "arch": cfg.name,
                "backend": f"pp{p}-inflight{d}", "tp": 1, "cp": 1,
                "pp": p, "paged": False, "chunk_size": None,
                "inflight": d, "num_slots": slots, "rate_req_s": 0.0,
                **s,
                "decode_ticks": occ["ticks"],
                "decode_tokens": occ["decode_tokens"],
                "tokens_per_tick": occ["tokens_per_tick"],
                "stage_busy_fraction": occ["stage_busy_fraction"],
                "busy_fraction_mean": occ["busy_fraction_mean"],
                "decode_rounds": len(dec),
                "predicted_ticks": pred_ticks,
                "predicted_busy_fraction":
                    pred_busy_rounds / pred_ticks if pred_ticks else 0.0,
                "boundary_bytes_per_round_measured":
                    sum(r.measured_transfers.get("bytes", 0) for r in dec)
                    / max(len(dec), 1),
                "boundary_bytes_per_round_predicted":
                    float(sum(o.total_msg_bytes for o in send)),
                "decode_collective_counts":
                    step_collective_counts(backend, OCC_GROUP),
                "token_checksum": checksum,
                "token_checksum_matches_depth1":
                    checksum == checksums[1],
            })

    # -- overload series: conservative vs optimistic admission on an
    #    oversubscribed pool, EOS-heavy closed trace (DESIGN.md §10).  Both
    #    policies serve the identical trace to completion (greedy decode is
    #    deterministic, so both produce identical token streams — the bench
    #    finishing IS the zero-MemoryError-escapes check); optimistic packs
    #    more live requests per fused step and pays in recompute passes.
    from repro.core.commodel import preemption_recompute_ops
    from repro.core.slo import predict_goodput

    ov_n = DRY_REQUESTS if dry_run else OV_REQUESTS
    otrace = make_poisson_trace(ov_n, 0.0, cfg.vocab_size,
                                prompt_lens=OV_PROMPT_LENS,
                                decode_lens=OV_DECODE_LENS, seed=13,
                                quantum=8, eos_prob=OV_EOS_PROB)
    pages_worst = -(-(OV_PROMPT_LENS[1] + OV_DECODE_LENS[1] - 1)
                    // PAGE_SIZE)
    # ~40% of worst-case parity: each request still fits alone (the
    # max() floor is the livelock-freedom condition — a lone survivor can
    # always finish), but the full slot set cannot, so optimistic
    # admission must actually preempt when the EOS-heavy mix's tail
    # requests run their whole budget
    ov_pages = 1 + max(pages_worst, num_slots * pages_worst * 2 // 5)
    owarm = sorted({r.prompt_len for r in otrace})
    eos_mean = float(np.mean([r.eos_pos if r.eos_pos is not None
                              else r.max_new_tokens for r in otrace]))
    for admission in ("conservative", "optimistic"):
        backend = make_backend("gspmd", cfg, params, num_slots=num_slots,
                               max_len=OV_MAX_LEN, paged=True,
                               page_size=PAGE_SIZE, num_pages=ov_pages)
        sched = lambda: Scheduler(backend, admission=admission)
        wrng = np.random.default_rng(1)
        sched().run([Request(rid=10_000 + j,
                             prompt=wrng.integers(2, cfg.vocab_size, s),
                             max_new_tokens=2)
                     for j, s in enumerate(owarm)])
        report = sched().run(otrace)
        s = report.summary()
        decode_steps = len([r for r in report.steps
                            if r.phase == "decode"])
        gp = predict_goodput(
            cfg, sum(OV_PROMPT_LENS) // 2, sum(OV_DECODE_LENS) // 2,
            num_slots=num_slots,
            capacity_tokens=(ov_pages - 1) * PAGE_SIZE,
            eos_mean=eos_mean, admission=admission)
        results.append({
            "series": "overload", "arch": cfg.name,
            "backend": f"gspmd-paged-{admission}", "tp": 1, "cp": 1,
            "pp": 1, "paged": True, "chunk_size": None, "inflight": 1,
            "admission": admission, "num_slots": num_slots,
            "rate_req_s": 0.0, **s,
            "pool_pages": ov_pages, "eos_prob": OV_EOS_PROB,
            "decode_steps": decode_steps,
            "recompute_steps": len([r for r in report.steps
                                    if r.phase == "recompute"]),
            # deterministic packing metric: counts are clock-independent on
            # a closed trace, so this is gatable (within one file) while
            # wall-clock throughput is not
            "tokens_per_decode_step":
                s["total_tokens"] / max(decode_steps, 1),
            "decode_collective_counts":
                step_collective_counts(backend, 1),
            # recompute collectives == a prefill's (counts are prefix-
            # length-invariant; only bytes scale)
            "recompute_collective_counts":
                _count(preemption_recompute_ops(cfg, 32, 1, 1,
                                                gather_mode="allgather")),
            "predicted_goodput_tok_s": gp.goodput_tok_s,
            "predicted_preempt_rate": gp.preempt_rate,
        })

    # -- prefix-cache series: the SAME template-heavy closed trace served
    #    cold and with the cross-request prefix index (DESIGN.md §13).
    #    The warm pass (rids 10_000+, identical prompts) compiles every
    #    chunk shape off the clock AND — on the cached backend — populates
    #    the index, so the measured pass hits on every request: the clean
    #    executed-vs-skipped comparison.  All gated quantities are either
    #    deterministic counts or within-file TTFT orderings.
    import hashlib

    from repro.core.commodel import prefix_cache_ops
    from repro.runtime.request import make_template_trace

    pc_n = DRY_REQUESTS if dry_run else PC_REQUESTS
    pc_tmpl = PC_TEMPLATE_PAGES * PAGE_SIZE
    pc_chunk = PAGE_SIZE
    pc_trace = make_template_trace(
        pc_n, 0.0, cfg.vocab_size, n_templates=2, template_len=pc_tmpl,
        suffix_lens=PC_SUFFIX_LENS, decode_lens=PC_DECODE_LENS, seed=17)
    pc_checksum = {}
    pc_ttft = {}
    # canonical closed form at the modal request shape (hit = the whole
    # template, suffix = mean suffix): drift-gated against the baseline
    pc_ops = prefix_cache_ops(cfg, pc_tmpl, sum(PC_SUFFIX_LENS) // 2,
                              chunk=pc_chunk, t=2, gather_mode="allgather")
    for cached in (False, True):
        backend = make_backend("tp", cfg, params, num_slots=num_slots,
                               max_len=PC_MAX_LEN, t=2, paged=True,
                               page_size=PAGE_SIZE, prefix_cache=cached)
        sched = lambda: Scheduler(backend, chunk_size=pc_chunk)
        sched().run([Request(rid=10_000 + i, prompt=r.prompt.copy(),
                             max_new_tokens=2) for i, r in
                     enumerate(pc_trace)])
        report = sched().run(pc_trace)
        s = report.summary()
        toks = report.tokens_by_rid()
        pc_checksum[cached] = hashlib.sha256(
            json.dumps(toks, sort_keys=True).encode()).hexdigest()
        pc_ttft[cached] = {m.rid: m.ttft for m in report.metrics}
        hits = {m.rid: m.cached_prefix_len for m in report.metrics
                if m.cached_prefix_len > 0}
        chunks = [r for r in report.steps if r.phase == "prefill"]
        executed = {}
        for r in chunks:
            for k, v in r.collective_counts.items():
                executed[k] = executed.get(k, 0) + v
        # per-request suffix arithmetic: ceil((s_p - hit) / chunk) passes
        pred_chunks = sum(
            -(-(m.prompt_len - m.cached_prefix_len) // pc_chunk)
            for m in report.metrics)
        per_chunk = chunk_counts(backend, pc_chunk)
        hit_rids = sorted(hits)
        drained = True
        if cached:
            backend.prefix_index.clear()
            drained = (backend.pool.stats().used_tokens == 0
                       and backend.pool.free_pages
                       == backend.pool.num_pages - 1)
        results.append({
            "series": "prefix-cache", "arch": cfg.name,
            "backend": "tp2-paged-prefix" if cached else "tp2-paged",
            "tp": 2, "cp": 1, "pp": 1, "paged": True,
            "chunk_size": pc_chunk, "inflight": 1,
            "num_slots": num_slots, "rate_req_s": 0.0, **s,
            "prefix_cache": cached, "template_len": pc_tmpl,
            "hits": len(hits),
            "hit_rate_measured": len(hits) / len(pc_trace),
            "cached_prefix_tokens": sum(hits.values()),
            "prefill_chunks": len(chunks),
            "predicted_prefill_chunks": pred_chunks,
            "executed_prefill_counts": executed,
            "predicted_executed_prefill_counts":
                {k: v * pred_chunks for k, v in per_chunk.items()},
            "prefill_chunk_counts": per_chunk,
            "decode_collective_counts":
                step_collective_counts(backend, 1),
            "prefix_cache_ops_executed_counts": pc_ops.executed_counts,
            "prefix_cache_ops_skipped_counts": pc_ops.skipped_counts,
            "ttft_hit_mean_s": float(np.mean(
                [pc_ttft[cached][r] for r in hit_rids]))
                if cached and hit_rids else None,
            "ttft_cold_mean_s": float(np.mean(
                [pc_ttft[False][r] for r in hit_rids]))
                if cached and hit_rids else None,
            "token_checksum": pc_checksum[cached],
            "token_checksum_matches_uncached":
                pc_checksum[cached] == pc_checksum[False],
            "pool_drained": drained,
            "index_stats":
                backend.prefix_index.stats() if cached else None,
        })

    # -- disagg-mixed series: the §14 acceptance bench.  The SAME seeded
    #    mixed trace three ways; every checksum below is over token
    #    streams, so "disagg changes nothing but the schedule" is gated
    #    bitwise, and the handoff volume is gated against the closed form
    #    (the scheduler itself asserts measured == predicted per ship).
    from repro.core.planner import TrafficClass, recommend_disagg
    from repro.runtime.scheduler import DisaggScheduler

    dm_chat_n = DRY_REQUESTS if dry_run else DM_CHAT_REQUESTS
    dm_long_n = 2 if dry_run else DM_LONG_REQUESTS
    dm_long_lens = (96, 128) if dry_run else DM_LONG_PROMPTS
    dm_long_quantum = 32 if dry_run else 64
    dm_max = 160 if dry_run else DM_MAX_LEN
    dm_rates = (0.0, 0.0) if dry_run else (DM_CHAT_RATE, DM_LONG_RATE)
    dm_chat = make_poisson_trace(dm_chat_n, dm_rates[0], cfg.vocab_size,
                                 prompt_lens=DM_CHAT_PROMPTS,
                                 decode_lens=DM_CHAT_DECODE, seed=29,
                                 quantum=8)
    dm_long = make_poisson_trace(dm_long_n, dm_rates[1], cfg.vocab_size,
                                 prompt_lens=dm_long_lens,
                                 decode_lens=DM_LONG_DECODE, seed=31,
                                 quantum=dm_long_quantum)
    for r in dm_long:
        r.rid += 100                         # chat rids < 100, longs >= 100
    dm_mixed = sorted(dm_chat + dm_long, key=lambda r: (r.arrival, r.rid))
    dm_warm = sorted({r.prompt_len for r in dm_mixed})

    def dm_backend(slots, owner_base=0, prefix=False, pool=None):
        return make_backend("gspmd", cfg, params, num_slots=slots,
                            max_len=dm_max, paged=True,
                            page_size=PAGE_SIZE, num_pages=DM_PAGES,
                            prefix_cache=prefix, pool=pool,
                            owner_base=owner_base)

    def dm_warm_reqs():
        wrng = np.random.default_rng(1)
        return [Request(rid=10_000 + j,
                        prompt=wrng.integers(2, cfg.vocab_size, s),
                        max_new_tokens=2)
                for j, s in enumerate(dm_warm)]

    def dm_stats(metrics, chat_only=False):
        ms = [m for m in metrics if not chat_only or m.rid < 100]
        tpots = [m.tpot for m in ms if m.num_generated > 1]
        return {
            "chat_tpot_mean_s": float(np.mean(tpots)),
            "chat_tpot_p99_s": float(np.percentile(tpots, 99)),
            "chat_ttft_p95_s": float(np.percentile(
                [m.ttft for m in ms], 95)),
        }

    def dm_checksum(toks, chat_only=False):
        sub = {k: v for k, v in toks.items()
               if not chat_only or int(k) < 100}
        return hashlib.sha256(
            json.dumps(sub, sort_keys=True).encode()).hexdigest()

    dm_records = {}
    for mode in ("chat-only", "colocated", "disagg"):
        trace = dm_chat if mode == "chat-only" else dm_mixed
        if mode == "disagg":
            dec = dm_backend(DM_SLOTS, prefix=True)
            pre = dm_backend(1, owner_base=DM_SLOTS, pool=dec.pool)
            sched = lambda: DisaggScheduler(pre, dec,
                                            chunk_size=CHUNK_SIZE,
                                            route_prompt_len=DM_ROUTE)
            sched().run(dm_warm_reqs())
            dec.prefix_index.clear()         # warm entries must not hit
        else:
            backend = dm_backend(DM_SLOTS)
            sched = lambda: Scheduler(backend, chunk_size=CHUNK_SIZE)
            sched().run(dm_warm_reqs())
        report = sched().run(trace)
        s = report.summary()
        toks = report.tokens_by_rid()
        rec = {
            "series": "disagg-mixed", "arch": cfg.name, "backend": mode,
            "tp": 1, "cp": 1, "pp": 1, "paged": True,
            "chunk_size": CHUNK_SIZE, "inflight": 1,
            "num_slots": DM_SLOTS, "rate_req_s": dm_rates[0], **s,
            **dm_stats(report.metrics, chat_only=True),
            "decode_collective_counts": step_collective_counts(
                dec if mode == "disagg" else backend, 1),
            "prefill_chunk_counts": chunk_counts(
                dec if mode == "disagg" else backend, CHUNK_SIZE),
            "token_checksum": dm_checksum(toks),
            "chat_token_checksum": dm_checksum(toks, chat_only=True),
        }
        if mode == "disagg":
            dm = s["disagg"]
            drained_ok = True
            dec.prefix_index.clear()
            drained_ok = (dec.pool.stats().used_tokens == 0
                          and dec.pool.free_pages
                          == dec.pool.num_pages - 1)
            # the decision rule the bench motivates, scored by the
            # analytical §14 planner at serving scale (closed form —
            # deterministic, drift-gated)
            full = get_config(ARCH)
            mixed_cls = [TrafficClass("chat", 24, 128, 4.0),
                         TrafficClass("summarize", 2048, 32, 0.6)]
            best_mixed = recommend_disagg(full, 8, mixed_cls)
            best_chat = recommend_disagg(full, 8, mixed_cls[:1])
            rec.update({
                "handoffs": dm["handoffs"],
                "handoff_pages": dm["handoff_pages"],
                "handoff_bytes": dm["handoff_bytes"],
                "predicted_handoff_bytes": dm["predicted_handoff_bytes"],
                "pool_drained": drained_ok,
                "planner_mixed_mode": best_mixed.mode,
                "planner_chat_mode": best_chat.mode,
            })
        dm_records[mode] = rec
        results.append(rec)
    print("SERVEJSON:" + json.dumps(results))


def _run_subprocess(dry_run: bool = False):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + REPO
    cmd = [sys.executable, "-m", "benchmarks.serving_bench", "--measure"]
    if dry_run:
        cmd.append("--dry-run")
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           cwd=REPO, timeout=1800)
    except subprocess.TimeoutExpired:
        return None, "timeout after 1800s"
    for line in r.stdout.splitlines():
        if line.startswith("SERVEJSON:"):
            return json.loads(line[len("SERVEJSON:"):]), None
    return None, r.stderr[-300:]


def rows(dry_run: bool = False):
    recs, err = _run_subprocess(dry_run)
    if recs is None:
        raise RuntimeError(f"serving_bench child failed: {err}")
    path = DRY_PATH if dry_run else OUT_PATH
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(recs, f, indent=2, sort_keys=True)
    out = []
    for r in recs:
        rate = "closed" if not r["rate_req_s"] else f"{r['rate_req_s']:g}rps"
        out.append((
            f"serve/{r['series']}/{r['arch']}/t{r['tp']}p{r['pp']}/"
            f"{r['backend']}/{rate}",
            r["throughput_tok_s"],
            f"tok_per_s={r['throughput_tok_s']:.1f};"
            f"ttft_p95={r['ttft_p95_s']*1e3:.0f}ms;"
            f"tpot_mean={r['tpot_mean_s']*1e3:.1f}ms;"
            f"e2e_p95={r['e2e_p95_s']:.2f}s"))
    return out


def main(dry_run: bool = False):
    # mirror the knobs _measure actually uses in each mode
    mode = (f"dry-run smoke, {DRY_REQUESTS} reqs, {DRY_SLOTS} slots"
            if dry_run
            else f"{N_REQUESTS} reqs × {RATES}, {NUM_SLOTS} slots")
    print(f"Continuous-batching serving — gspmd/tp2/pp2 + paged, short, "
          f"long-context & overload-admission traces ({mode}, "
          f"Poisson arrivals)")
    for r in rows(dry_run):
        print(f"  {r[0]:60s} {r[2]}")
    out = DRY_PATH if dry_run else OUT_PATH
    if os.path.exists(out):
        print(f"  wrote {out}")


if __name__ == "__main__":
    if "--measure" in sys.argv:
        _measure(dry_run="--dry-run" in sys.argv)
    else:
        main(dry_run="--dry-run" in sys.argv)
