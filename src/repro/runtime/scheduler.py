"""Continuous-batching scheduler over a DecodeBackend (paper §V-C, serving).

vLLM-style iteration-level scheduling, reduced to the pieces the paper's SLO
study actually exercises: a fixed pool of KV-cache *slots*, admission of
queued requests into freed slots between decode steps (each admission is one
batch-1 prefill scattered into the slot row), one fused decode step per
iteration over the whole slot batch with per-sequence positions, and
EOS/length-based eviction.

Robustness layer (DESIGN.md §10).  The scheduler survives the traffic mixes
that oversubscribe it instead of only modeling the sunny day:

  * **Admission policy.**  ``admission="conservative"`` (default) commits
    every paged request's worst-case decode budget up front — mid-decode
    page exhaustion is impossible, but EOS-heavy traffic strands pool
    capacity on budgets that never materialize.  ``admission="optimistic"``
    admits on *current* need (the prompt's pages) and recovers from the
    resulting pressure by preemption.
  * **Preemption-by-recompute.**  When ``KVPool.extend`` hits
    ``MemoryError`` mid-decode, the youngest active request is preempted:
    pages and slot freed, the request requeued *retaining its generated
    tokens*.  Re-admission re-prefills prompt + generated prefix in one
    pass; greedy decode is deterministic, so the recompute's final-position
    token must equal the last token generated before preemption (asserted
    at runtime — the token-identity invariant), and the stream continues
    bitwise identical to an uninterrupted run.  Each recompute pass is
    logged as a phase="recompute" StepRecord carrying the predicted prefill
    collectives of the prefix (``commodel.preemption_recompute_ops``) next
    to the measured PP transfers.
  * **Deadlines & cancellation.**  ``Request.deadline`` /
    ``ttft_deadline`` shed hopeless requests mid-flight
    (finish_reason="deadline"); ``Scheduler.cancel(rid)`` shed them on
    demand ("cancelled").
  * **Fault tolerance.**  With a ``runtime.faults.FaultInjector`` attached,
    injected faults at the decode/prefill/pool/pp_transfer sites are
    absorbed: transient failures retry with exponential backoff (visible on
    the virtual clock), permanent ones finish the affected requests with
    finish_reason="error", injected pool exhaustion takes the preemption
    path, and transfer delays stretch the clock.

The scheduler measures the quantities ``core.slo.predict_slo`` predicts —
per-request TTFT / TPOT / E2E — and records per-step communication: predicted
collective counts/bytes from ``commodel.comm_ops_for`` plus, for pipeline
backends, the engine's measured boundary TransferRecords.  The paper's claim
that per-step collective *counts* are batch-invariant (only message bytes
scale with batch) is load-bearing here — it is what makes a fixed-capacity
decode step correct for a varying active set — so it is asserted against
``comm_ops_for(batch=...)`` at construction time.
"""
from __future__ import annotations

import bisect
import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.commodel import kv_handoff_ops, kv_handoff_pages
from repro.runtime.backends import DecodeBackend
from repro.runtime.faults import PermanentFault, TransientFault
from repro.runtime.request import Request, RequestMetrics
from repro.runtime.schedule import RoundResult, make_queue
from repro.runtime.tracing import span


# ---------------------------------------------------------------------------
# clocks (injectable so tests run on virtual time)
# ---------------------------------------------------------------------------


class WallClock:
    """Real time, relative to construction; ``wait_until`` sleeps."""

    def __init__(self):
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def wait_until(self, t: float) -> None:
        delta = t - self.now()
        if delta > 0:
            time.sleep(delta)


class VirtualClock:
    """Deterministic clock for tests: time only moves via ``wait_until`` /
    ``advance`` — decode steps take zero virtual time."""

    def __init__(self):
        self.t = 0.0

    def now(self) -> float:
        return self.t

    def wait_until(self, t: float) -> None:
        self.t = max(self.t, t)

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# per-step traffic records
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StepRecord:
    """Communication of one scheduler iteration: one decode *round* (one
    microbatch group through the instruction queue — the whole slot batch on
    fused backends), one prefill chunk (chunked-prefill mode, DESIGN.md §8),
    or one preemption's recompute pass (DESIGN.md §10).

    ``wall_s`` and the per-stage ``stage_busy``/``stage_idle`` tick deltas
    (DESIGN.md §11) make schedule occupancy a *measured* quantity: summing
    the deltas over a run reproduces the queue's busy/idle totals, and
    ``busy/(busy+idle)`` per stage is the measured bubble occupancy next to
    the ``commodel.pp_schedule_stats`` prediction."""

    step: int
    n_active: int
    collective_counts: Dict[str, int]     # predicted, per decode round/pass
    predicted_wire_bytes: float           # at the group batch (decode) / 1
    measured_transfers: Dict[str, int]    # PP boundary hops of this round
    phase: str = "decode"                 # "decode" | "prefill" | "recompute"
    rid: Optional[int] = None             # request, for prefill/recompute
    prefix_len: Optional[int] = None      # recomputed positions (recompute)
    cached_prefix_len: Optional[int] = None   # prefix-cache hit (prefill,
    #                                           DESIGN.md §13): positions
    #                                           adopted instead of computed
    wall_s: float = 0.0                   # its span's duration (decode
    #                                       round: sched.round; chunk:
    #                                       sched.chunk; runtime/tracing.py)
    stage_busy: Optional[List[int]] = None   # per-stage busy ticks (decode)
    stage_idle: Optional[List[int]] = None   # per-stage idle ticks (decode)


def step_collective_counts(backend: DecodeBackend,
                           batch: int = 1) -> Dict[str, int]:
    """Collective call counts of ONE decode step, summed by collective."""
    counts: Dict[str, int] = {}
    for o in backend.decode_comm_ops(batch=batch):
        counts[o.collective] = counts.get(o.collective, 0) + o.count
    return counts


def assert_counts_batch_invariant(backend: DecodeBackend) -> None:
    """The paper's batch-invariance property, asserted: a decode step issues
    the same number of collectives at any batch size — only wire bytes scale
    (linearly).  The scheduler's fixed-capacity step depends on this."""
    base = backend.decode_comm_ops(batch=1)
    for batch in (2, backend.num_slots):
        if batch < 2:
            continue
        scaled = backend.decode_comm_ops(batch=batch)
        if step_collective_counts(backend, 1) != \
                step_collective_counts(backend, batch):
            raise AssertionError(
                f"per-step collective counts vary with batch={batch}: "
                f"{step_collective_counts(backend, 1)} vs "
                f"{step_collective_counts(backend, batch)}")
        for o1, ob in zip(base, scaled):
            if not np.isclose(ob.wire_bytes, batch * o1.wire_bytes):
                raise AssertionError(
                    f"wire bytes not linear in batch for {o1.collective}: "
                    f"{ob.wire_bytes} != {batch} * {o1.wire_bytes}")


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------


_NORMAL_FINISH = ("length", "eos")


@dataclasses.dataclass
class ServingReport:
    metrics: List[RequestMetrics]
    steps: List[StepRecord]
    wall_time: float

    @property
    def total_tokens(self) -> int:
        return sum(m.num_generated for m in self.metrics)

    @property
    def throughput(self) -> float:
        return self.total_tokens / self.wall_time if self.wall_time else 0.0

    @property
    def preemptions(self) -> int:
        return sum(m.preemptions for m in self.metrics)

    @property
    def retries(self) -> int:
        return sum(m.retries for m in self.metrics)

    def tokens_by_rid(self) -> Dict[int, List[int]]:
        return {m.rid: list(m.tokens) for m in self.metrics}

    def occupancy(self) -> dict:
        """Measured decode-schedule occupancy (DESIGN.md §11), aggregated
        over the decode StepRecords' per-stage busy/idle tick deltas:
        schedule ticks, per-stage busy fractions, decode tokens per tick.
        Deterministic — the schedule clock, not wall time — so the
        pp-occupancy bench series can gate it exactly."""
        recs = [r for r in self.steps
                if r.phase == "decode" and r.stage_busy is not None]
        if not recs:
            return {"ticks": 0, "decode_tokens": 0, "tokens_per_tick": 0.0,
                    "stage_busy_fraction": [], "busy_fraction_mean": 0.0}
        n = len(recs[0].stage_busy)
        busy = [sum(r.stage_busy[s] for r in recs) for s in range(n)]
        idle = [sum(r.stage_idle[s] for r in recs) for s in range(n)]
        ticks = busy[0] + idle[0]   # every stage is busy or idle each tick
        frac = [b / max(b + i, 1) for b, i in zip(busy, idle)]
        # n_active at record time == tokens appended by that round
        dec_tokens = sum(r.n_active for r in recs)
        return {"ticks": ticks,
                "decode_tokens": dec_tokens,
                "tokens_per_tick": dec_tokens / ticks if ticks else 0.0,
                "stage_busy_fraction": frac,
                "busy_fraction_mean": float(np.mean(frac))}

    def summary(self) -> dict:
        def _pct(vals, q):
            return float(np.percentile(vals, q)) if vals else 0.0

        # shed requests may never have produced a first token — keep their
        # zero-initialized first_token out of the TTFT statistics
        ttfts = [m.ttft for m in self.metrics if m.num_generated > 0]
        tpots = [m.tpot for m in self.metrics if m.num_generated > 1]
        e2es = [m.e2e for m in self.metrics]
        return {
            "requests": len(self.metrics),
            "total_tokens": self.total_tokens,
            "wall_time_s": self.wall_time,
            "throughput_tok_s": self.throughput,
            "ttft_mean_s": float(np.mean(ttfts)) if ttfts else 0.0,
            "ttft_p95_s": _pct(ttfts, 95),
            "tpot_mean_s": float(np.mean(tpots)) if tpots else 0.0,
            "tpot_p95_s": _pct(tpots, 95),
            "e2e_mean_s": float(np.mean(e2es)) if e2es else 0.0,
            "e2e_p95_s": _pct(e2es, 95),
            "preemptions": self.preemptions,
            "retries": self.retries,
            "shed": len([m for m in self.metrics
                         if m.finish_reason not in _NORMAL_FINISH]),
        }


@dataclasses.dataclass
class _Active:
    req: Request
    metrics: RequestMetrics
    seq: int = 0                  # admission sequence (preemption order)


@dataclasses.dataclass
class _Prefilling:
    """A request whose prompt (or recompute prefix) is mid-way through
    chunked prefill."""

    req: Request
    metrics: RequestMetrics
    prefix: np.ndarray            # tokens being prefilled (prompt, or
    #                               prompt + generated prefix on recompute)
    done: int = 0                 # prefix positions already prefilled
    resume: Optional[List[int]] = None   # generated tokens (recompute only)
    cached: int = 0               # prefix-cache hit length: positions
    #                               adopted at admission, never computed —
    #                               chunking starts at done == cached


class Scheduler:
    """Continuous batching over ``backend.num_slots`` KV-cache slots.

    One ``step()`` = shed expired requests, admit every arrived request a
    free slot can take (batch-1 prefill each, TTFT stamped), then ONE fused
    decode step over the full slot batch with per-sequence positions, then
    eviction of finished sequences (EOS or length), freeing their slots for
    the next iteration's admissions.

    ``chunk_size`` (paged backends only, DESIGN.md §8) turns prefill into
    *chunked* prefill: admission only allocates the slot's pages, and each
    iteration advances ONE prefilling request by one ``chunk_size``-token
    pass before the decode step — so a long prompt no longer stalls running
    slots for its whole prefill, only for one chunk.  Iterations with no
    decoding slot skip the jitted decode step entirely (nothing useful would
    run in it) and just advance prefill / wait for the next arrival.

    ``admission`` ("conservative" | "optimistic"), ``faults``,
    ``retry_limit`` and ``retry_backoff`` are the robustness knobs —
    DESIGN.md §10 and the module docstring.
    """

    def __init__(self, backend: DecodeBackend, clock=None,
                 chunk_size: int = None, admission: str = "conservative",
                 faults=None, retry_limit: int = 3,
                 retry_backoff: float = 0.05):
        self.backend = backend
        self.clock = clock if clock is not None else WallClock()
        self.num_slots = backend.num_slots
        self.queue: List[Request] = []     # sorted by arrival, FIFO in ties
        self.free: List[int] = list(range(self.num_slots))
        self.active: Dict[int, _Active] = {}
        self.prefilling: Dict[int, _Prefilling] = {}   # slot -> state (FIFO)
        self.chunk_size = chunk_size
        if admission not in ("conservative", "optimistic"):
            raise ValueError(
                f"admission must be 'conservative' or 'optimistic', "
                f"got {admission!r}")
        if admission == "optimistic" and not getattr(backend, "paged", False):
            raise ValueError(
                "optimistic admission relaxes the KV-page commitment; "
                "contiguous slot backends have nothing to overcommit — "
                "construct the backend with paged=True")
        self.admission = admission
        self.faults = faults
        if retry_limit < 0:
            raise ValueError(f"retry_limit must be >= 0, got {retry_limit}")
        self.retry_limit = int(retry_limit)
        self.retry_backoff = float(retry_backoff)
        if chunk_size is not None:
            if chunk_size < 1:
                raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
            if not getattr(backend, "paged", False):
                raise ValueError(
                    "chunked prefill writes straight into KV pages; "
                    "construct the backend with paged=True")
            if getattr(backend, "c", 1) > 1:
                raise ValueError(
                    "chunked prefill and context parallelism are "
                    "alternative long-prompt strategies (DESIGN.md §9); "
                    "a c>1 backend prefills monolithically")
            # per-chunk counts are chunk-length-invariant (commodel.
            # chunked_prefill_ops) — compute once at the nominal size
            self._chunk_counts = self._count(
                backend.chunk_comm_ops(chunk_size))
        self.tokens = np.zeros(self.num_slots, np.int32)
        self.pos = np.zeros(self.num_slots, np.int64)
        self.finished: List[RequestMetrics] = []
        self.step_log: List[StepRecord] = []
        self._step_i = 0
        self._rids: set = set()            # every rid this run has seen
        self._preempted: Dict[int, RequestMetrics] = {}  # rid -> metrics
        self._adm_seq = 0
        self._total_tokens = 0
        self._last_sig = None
        self._idle_iters = 0
        # the batch-invariance the fixed-capacity step relies on (paper
        # Tables III–VI: no batch term in any count column)
        assert_counts_batch_invariant(backend)
        self._step_counts = step_collective_counts(backend, 1)
        # the engine's instruction queue (DESIGN.md §11): decode no longer
        # calls backend.decode_step directly — rounds are begun per
        # microbatch group and pumped through the queue
        self._queue = make_queue(backend)
        self._group_size = self._queue.group_size
        self._step_bytes = sum(
            o.wire_bytes
            for o in backend.decode_comm_ops(batch=self._group_size))

    @staticmethod
    def _count(ops) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for o in ops:
            counts[o.collective] = counts.get(o.collective, 0) + o.count
        return counts

    # ------------------------------------------------------------- intake
    def submit(self, requests) -> None:
        reqs = [requests] if isinstance(requests, Request) else list(requests)
        paged = getattr(self.backend, "paged", False)
        c = getattr(self.backend, "c", 1)
        seen: set = set()
        for r in reqs:
            if r.rid in self._rids or r.rid in seen:
                raise ValueError(
                    f"duplicate rid {r.rid}: already submitted this run "
                    f"(per-request metrics and token streams key on rid)")
            seen.add(r.rid)
            # the last generated token is never fed back, so the highest
            # cache position written is prompt_len + max_new_tokens - 2;
            # CP pads the prompt to a multiple of c (DESIGN.md §9)
            need = max(r.prompt_len + r.max_new_tokens - 1,
                       -(-r.prompt_len // c) * c)
            w = self.backend.cfg.sliding_window
            if need > self.backend.max_len and not w:
                raise ValueError(
                    f"request {r.rid} needs {need} cache positions "
                    f"> max_len {self.backend.max_len}")
            if paged:
                # a request the EMPTY pool couldn't hold would never pass
                # the admission gate — reject it up front, don't deadlock
                need_pages = -(-need // self.backend.page_size)
                usable = self.backend.pool.num_pages - 1    # minus scratch
                if need_pages > usable:
                    raise ValueError(
                        f"request {r.rid} needs {need_pages} pages "
                        f"> pool capacity {usable}")
        for r in reqs:
            self._rids.add(r.rid)
            self._enqueue(r)

    def _enqueue(self, req: Request) -> None:
        """Sorted insert by arrival time — O(log n) search + one list
        insert, replacing the old full re-sort per submit.  ``insort`` is
        right-biased, so equal arrivals keep FIFO submission order (and a
        preempted request requeues behind same-arrival peers)."""
        bisect.insort(self.queue, req, key=lambda r: r.arrival)

    # ------------------------------------------------------------- lifecycle
    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it currently lives — queued, mid-
        prefill, or actively decoding.  Generated tokens are kept and the
        request finishes with ``finish_reason="cancelled"``.  Returns False
        when the rid is unknown or already finished (cancellation raced
        completion — the tokens already exist either way)."""
        now = self.clock.now()
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                self._shed_queued(req, "cancelled", now)
                return True
        for slot, st in list(self.prefilling.items()):
            if st.req.rid == rid:
                self._abort_prefill(slot, "cancelled", now)
                return True
        for slot, st in list(self.active.items()):
            if st.req.rid == rid:
                # complete any in-flight round first: freeing the slot (and
                # its pages) under a round that still writes them would
                # corrupt a concurrent group's schedule (DESIGN.md §11)
                self._drain_queue()
                if slot in self.active and self.active[slot].req.rid == rid:
                    self._finish(slot, "cancelled", self.clock.now())
                return True         # tokens exist either way (drain may
                #                     have finished the request normally)
        return False

    @staticmethod
    def _expired(req: Request, now: float, pre_first_token: bool) -> bool:
        if req.deadline is not None and now > req.arrival + req.deadline:
            return True
        return pre_first_token and req.ttft_deadline is not None \
            and now > req.arrival + req.ttft_deadline

    def _shed_queued(self, req: Request, reason: str, now: float) -> None:
        """Finish a request straight out of the queue (deadline/cancel).
        A preempted request keeps the tokens it generated before eviction."""
        m = self._preempted.pop(req.rid, None)
        if m is None:
            m = RequestMetrics(rid=req.rid, prompt_len=req.prompt_len,
                               arrival=req.arrival)
        m.finished = now
        m.finish_reason = reason
        self.finished.append(m)

    def _abort_prefill(self, slot: int, reason: str, now: float) -> None:
        st = self.prefilling.pop(slot)
        self.backend.free_slots([slot])
        self.free.append(slot)
        st.metrics.finished = now
        st.metrics.finish_reason = reason
        self.finished.append(st.metrics)

    def _shed_expired(self, now: float) -> None:
        """Drop every queued / mid-prefill request that can no longer meet
        its deadline — head-of-line or not, capacity spent on it is wasted."""
        for req in [r for r in self.queue
                    if self._expired(r, now, r.rid not in self._preempted)]:
            self.queue.remove(req)
            self._shed_queued(req, "deadline", now)
        for slot, st in list(self.prefilling.items()):
            if self._expired(st.req, now, st.resume is None):
                self._abort_prefill(slot, "deadline", now)

    # ------------------------------------------------------------- faults
    def _apply_fault(self, site: str) -> None:
        if self.faults is None:
            return
        f = self.faults.draw(site)
        if f is None:
            return
        if f.kind == "delay":
            self.clock.wait_until(self.clock.now() + f.delay_s)
        elif f.kind == "oom":
            raise MemoryError(f"injected fault at {site}")
        elif f.kind == "transient":
            raise TransientFault(f"injected fault at {site}")
        else:
            raise PermanentFault(f"injected fault at {site}")

    def _backoff(self, attempt: int) -> None:
        """Exponential backoff before retry attempt N (1-based), on the
        scheduler clock so virtual-clock tests see the waits."""
        self.clock.wait_until(self.clock.now()
                              + self.retry_backoff * 2.0 ** (attempt - 1))

    # ------------------------------------------------------------- admission
    def _finish(self, slot: int, reason: str, now: float) -> None:
        st = self.active.pop(slot)
        st.metrics.finished = now
        st.metrics.finish_reason = reason
        self.finished.append(st.metrics)
        self.backend.free_slots([slot])
        self.free.append(slot)
        self.tokens[slot] = 0
        self.pos[slot] = 0

    def _preempt_youngest(self) -> None:
        """Evict the most recently admitted active request: free its pages
        and slot, requeue it retaining its generated tokens (re-admission
        recomputes the prefix — DESIGN.md §10).

        With rounds in flight (DESIGN.md §11) victims come from groups with
        NO issued work: freeing pages a busy round still writes would let a
        subsequent ``start_round`` re-allocate them mid-write.  The group
        whose ``start_round`` raised MemoryError is never busy and holds at
        least one active slot, so a safe candidate always exists; at depth
        1 every group is idle here and this reduces to the old global
        youngest-first rule."""
        busy = self._queue.busy_groups()
        cands = [s for s in self.active if s // self._group_size not in busy]
        slot = max(cands or self.active,
                   key=lambda s: self.active[s].seq)
        st = self.active.pop(slot)
        st.metrics.preemptions += 1
        self._preempted[st.req.rid] = st.metrics
        self.backend.free_slots([slot])
        self.free.append(slot)
        self.tokens[slot] = 0
        self.pos[slot] = 0
        self._enqueue(st.req)

    def _run_prefill(self, slot: int, prefix: np.ndarray,
                     metrics: RequestMetrics,
                     start: int = 0) -> Optional[int]:
        """One whole-prefix prefill pass with fault injection + bounded
        retry; returns the final position's greedy token, or None when the
        request errored out (caller frees the slot).  ``start`` skips a
        prefix-cache hit's adopted positions (DESIGN.md §13) — a retry
        rewrites the same suffix rows, so it stays idempotent."""
        paged = getattr(self.backend, "paged", False)
        attempt = 0
        while True:
            try:
                self._apply_fault("prefill")
                if paged:
                    tok = int(self.backend.prefill_whole(slot, prefix,
                                                         start=start))
                    self.backend.finish_prefill(slot)
                else:
                    tok = int(self.backend.prefill_into_slots(
                        [prefix], [slot])[0])
                return tok
            except TransientFault:
                attempt += 1
                if attempt > self.retry_limit:
                    return None
                metrics.retries += 1
                self._backoff(attempt)
            except PermanentFault:
                return None

    def _stop_reason(self, req: Request,
                     metrics: RequestMetrics) -> Optional[str]:
        """Normal finish check after a token append: model EOS, emulated
        EOS (``eos_pos``), or exhausted decode budget."""
        if req.eos_id is not None and metrics.tokens[-1] == req.eos_id:
            return "eos"
        if req.eos_pos is not None and \
                metrics.num_generated >= req.eos_pos:
            return "eos"
        if metrics.num_generated >= req.max_new_tokens:
            return "length"
        return None

    def _admit_ready(self) -> None:
        paged = getattr(self.backend, "paged", False)
        optimistic = self.admission == "optimistic"
        while self.free and self.queue and \
                self.queue[0].arrival <= self.clock.now():
            req = self.queue[0]
            state = self._preempted.get(req.rid)
            if state is None:
                prefix_len = req.prompt_len
                budget = req.max_new_tokens
            else:
                # recompute prefix: prompt + all generated tokens but the
                # last (which was emitted, never fed back) — total worst
                # case positions are unchanged from first admission
                prefix_len = req.prompt_len + len(state.tokens) - 1
                budget = req.max_new_tokens - len(state.tokens) + 1
            if paged and not self.backend.can_admit(prefix_len, budget,
                                                    optimistic=optimistic):
                # a free slot but not enough pages: keep it queued
                # (head-of-line — admission order stays arrival order)
                # until evictions free pages.  Optimistic admission only
                # needs the prefix's pages now; the decode budget is
                # covered by preemption instead of reservation.
                break
            # Sync: complete in-flight rounds before the admission prefill
            # donates into caches/pages a round may still read (no-op at
            # depth 1 — nothing is ever in flight between steps)
            self._drain_queue()
            self.queue.pop(0)
            slot = self.free.pop(0)
            self._adm_seq += 1
            if state is None:
                m = RequestMetrics(rid=req.rid, prompt_len=req.prompt_len,
                                   arrival=req.arrival,
                                   admitted=self.clock.now())
                prefix = req.prompt
                resume = None
            else:
                m = self._preempted.pop(req.rid)
                prefix = np.concatenate(
                    [req.prompt, np.asarray(m.tokens[:-1], np.int32)])
                resume = list(m.tokens)
            hit = 0
            if paged:
                # prefix-cache lookup (DESIGN.md §13) covers fresh prompts
                # AND recompute prefixes: a recompute prefix begins with
                # the very prompt whose blocks the index pinned at first
                # admission, so re-admission adopts those pages and
                # recomputes only from the first novel position on.  The
                # §10 token-identity assertion stays honest — generated
                # tokens never enter the index (``cache_prefix`` indexes
                # prompts only), so a hit can never cover the recomputed
                # tail whose final token the assertion checks.
                if getattr(self.backend, "prefix_index", None) is not None:
                    hit = self.backend.begin_prefill_cached(slot, prefix,
                                                            budget)
                    if resume is None:
                        m.cached_prefix_len = hit
                else:
                    self.backend.begin_prefill(slot, len(prefix), budget)
                if self.chunk_size is not None:
                    self.prefilling[slot] = _Prefilling(req, m, prefix=prefix,
                                                        resume=resume,
                                                        done=hit, cached=hit)
                    continue
            if resume is not None:
                # isolate the recompute pass's measured boundary hops
                self.backend.drain_transfers()
            tok = self._run_prefill(slot, prefix, m, start=hit)
            if tok is None:
                self.backend.free_slots([slot])
                self.free.append(slot)
                m.finished = self.clock.now()
                m.finish_reason = "error"
                self.finished.append(m)
                continue
            self._queue.note_prefill(slot)
            now = self.clock.now()
            if resume is not None:
                self._log_recompute(req.rid, len(prefix), cached=hit)
                self._resume_active(slot, req, m, resume, len(prefix), tok)
                continue
            if paged and hasattr(self.backend, "cache_prefix"):
                # index the freshly committed prompt blocks (DESIGN.md §13)
                self.backend.cache_prefix(slot, req.prompt)
            m.first_token = now
            m.tokens.append(tok)
            self._total_tokens += 1
            self.active[slot] = _Active(req, m, seq=self._adm_seq)
            self.tokens[slot] = tok
            self.pos[slot] = req.prompt_len
            reason = self._stop_reason(req, m)
            if reason:
                self._finish(slot, reason, now)

    def _log_recompute(self, rid: int, prefix_len: int,
                       cached: int = 0) -> None:
        """Log one recompute pass.  A warm recompute (prefix-cache hit on
        re-admission) only executes ``prefix_len - cached`` positions, so
        predicted wire bytes scale with the honest suffix — counts are
        prefill-length-invariant either way."""
        ops = self.backend.prefill_comm_ops(prefix_len - cached)
        self.step_log.append(StepRecord(
            step=self._step_i, n_active=len(self.active),
            collective_counts=self._count(ops),
            predicted_wire_bytes=sum(o.wire_bytes for o in ops),
            measured_transfers=self.backend.drain_transfers(),
            phase="recompute", rid=rid, prefix_len=prefix_len,
            cached_prefix_len=cached or None))
        self._step_i += 1

    def _resume_active(self, slot: int, req: Request, m: RequestMetrics,
                       resume: List[int], prefix_len: int,
                       tok: int) -> None:
        """Rejoin the decoding set after a recompute pass.  The pass's
        final-position greedy token must be bitwise the last token the
        request generated before preemption — greedy decode is
        deterministic, so anything else means the recomputed KV diverged."""
        if tok != resume[-1]:
            raise RuntimeError(
                f"preemption token-identity violated for rid {req.rid}: "
                f"recompute of {prefix_len} positions produced token {tok}, "
                f"stream had {resume[-1]}")
        self.active[slot] = _Active(req, m, seq=self._adm_seq)
        self.tokens[slot] = resume[-1]
        self.pos[slot] = prefix_len
        # metrics keep their original admitted/first_token stamps: TTFT
        # already happened; preemption shows up in TPOT/E2E, where the
        # recompute actually costs

    def _advance_prefill(self) -> None:
        """Run ONE prefill chunk for the oldest mid-prefill request; on the
        final chunk the request's first token is stamped (TTFT) and the slot
        joins the decoding set.  A recompute prefix (``resume``) re-chunks
        the same way, logging phase="recompute" records."""
        # Sync: a chunk writes the slot's pages — no round may be mid-read
        self._drain_queue()
        slot = next(iter(self.prefilling))
        st = self.prefilling[slot]
        start = st.done
        end = min(start + self.chunk_size, len(st.prefix))
        attempt = 0
        while True:
            try:
                self._apply_fault("prefill")
                with span("sched.chunk", rid=st.req.rid,
                          tokens=end - start) as sp:
                    tok = self.backend.prefill_chunk(
                        slot, st.prefix[start:end], start)
                break
            except TransientFault:
                attempt += 1
                if attempt > self.retry_limit:
                    self._abort_prefill(slot, "error", self.clock.now())
                    return
                st.metrics.retries += 1
                self._backoff(attempt)
            except PermanentFault:
                self._abort_prefill(slot, "error", self.clock.now())
                return
        st.done = end
        self._queue.note_prefill(slot)
        self.step_log.append(StepRecord(
            step=self._step_i, n_active=len(self.active),
            collective_counts=dict(self._chunk_counts),
            predicted_wire_bytes=sum(
                o.wire_bytes
                for o in self.backend.chunk_comm_ops(end - start)),
            measured_transfers=self.backend.drain_transfers(),
            phase="prefill" if st.resume is None else "recompute",
            rid=st.req.rid,
            prefix_len=None if st.resume is None else len(st.prefix),
            cached_prefix_len=st.cached or None,
            wall_s=sp.seconds))
        self._step_i += 1
        if end < len(st.prefix):
            return
        del self.prefilling[slot]
        self.backend.finish_prefill(slot)
        now = self.clock.now()
        self._adm_seq += 1
        if st.resume is not None:
            self._resume_active(slot, st.req, st.metrics, st.resume,
                                len(st.prefix), int(tok))
            return
        if hasattr(self.backend, "cache_prefix"):
            # index the freshly committed prompt blocks (DESIGN.md §13)
            self.backend.cache_prefix(slot, st.req.prompt)
        st.metrics.first_token = now
        st.metrics.tokens.append(int(tok))
        self._total_tokens += 1
        self.active[slot] = _Active(st.req, st.metrics, seq=self._adm_seq)
        self.tokens[slot] = int(tok)
        self.pos[slot] = st.req.prompt_len
        reason = self._stop_reason(st.req, st.metrics)
        if reason:
            self._finish(slot, reason, now)

    # ------------------------------------------------------------- stepping
    def _error_active(self, why: str) -> None:
        now = self.clock.now()
        for slot in list(self.active):
            self._finish(slot, "error", now)

    def _refill_rounds(self) -> None:
        """Begin one decode round for every microbatch group that has at
        least one active slot and no round in flight.  On fused backends
        there is exactly one group spanning every slot — one round per
        iteration, the pre-refactor cadence."""
        G = self._group_size
        pending = self._queue.pending_groups()
        for g in range(self.num_slots // G):
            if g in pending:
                continue
            lo = g * G
            live = sum(1 for s in range(lo, lo + G) if s in self.active)
            if live:
                self._queue.begin_round(g, self.tokens, self.pos, live)

    def _pump_queue(self) -> Optional[List[RoundResult]]:
        """Refill + pump the instruction queue behind the recovery ladder
        (the fused era's ``_recovered_decode``): preemption on pool
        exhaustion, bounded backoff retries on transient faults, error-
        finish + queue abort on permanent ones.  Per-attempt fault draws
        keep the pre-refactor order (pp_transfer → pool → decode), so
        seeded fault schedules hit the same sites.  Returns the completed
        rounds, or None when this iteration's decode was abandoned."""
        attempt = 0
        while True:
            try:
                if self.faults is not None:
                    if self.backend.p > 1:
                        self._apply_fault("pp_transfer")
                    self._apply_fault("pool")
                    self._apply_fault("decode")
                self._refill_rounds()
                if not self._queue.in_flight:
                    return None
                return self._queue.pump()
            except MemoryError:
                if len(self.active) < 2:
                    # nothing else to preempt: the pages are held by
                    # mid-prefill slots (their owner frees them by
                    # finishing) or the fault was injected — stall this
                    # iteration instead of thrashing the lone request
                    return None
                self._preempt_youngest()
            except TransientFault:
                attempt += 1
                if attempt > self.retry_limit:
                    self._error_active("retries exhausted")
                    self._queue.abort_all()
                    return None
                for st in self.active.values():
                    st.metrics.retries += 1
                self._backoff(attempt)
            except PermanentFault:
                self._error_active("permanent fault")
                self._queue.abort_all()
                return None

    def _complete_round(self, res: RoundResult) -> None:
        """Land one completed round: record its traffic/occupancy and
        append its tokens to the slots still active (a slot preempted or
        cancelled mid-round is simply skipped — its instructions died with
        the round that carried them)."""
        now = self.clock.now()
        # n_active is the number of slots THIS round appends to — at depth 1
        # the single group spans every slot, so this equals len(self.active)
        # (the pre-queue semantic); at depth > 1 it is the group's live rows,
        # which is what ServingReport.occupancy() sums as decode tokens.
        appended = sum(1 for slot in res.slots if slot in self.active)
        self.step_log.append(StepRecord(
            step=self._step_i, n_active=appended,
            collective_counts=dict(self._step_counts),
            predicted_wire_bytes=self._step_bytes,
            measured_transfers=dict(res.transfers),
            wall_s=res.wall_s,
            stage_busy=list(res.stage_busy),
            stage_idle=list(res.stage_idle)))
        self._step_i += 1
        base = res.slots[0]
        for slot in res.slots:
            st = self.active.get(slot)
            if st is None:
                continue
            tok = int(res.tokens[slot - base])
            st.metrics.tokens.append(tok)
            self._total_tokens += 1
            self.tokens[slot] = tok
            self.pos[slot] += 1
            reason = self._stop_reason(st.req, st.metrics)
            if reason:
                self._finish(slot, reason, now)
            elif self._expired(st.req, now, pre_first_token=False):
                self._finish(slot, "deadline", now)

    def _drain_queue(self) -> None:
        """Complete every in-flight round (the ``Sync`` instruction) before
        any operation that mutates caches/pages a round may still touch —
        admission prefill, prefill chunk, cancellation.  No-op at depth 1:
        the fused queue never holds issued work between steps."""
        drained = bool(self._queue.in_flight)
        for res in self._queue.sync():
            self._complete_round(res)
        if drained:
            # round hops were attributed per round at send time; reset the
            # backend cursor so they don't leak into the next prefill/chunk
            # record's measured_transfers
            self.backend.drain_transfers()

    def step(self) -> bool:
        """One scheduler iteration; returns False when fully drained.  Its
        span carries the scheduler clock's reading at its start: the bridge
        from the spans' clock to the one that stamps requests."""
        with span("sched.step") as sp:
            sp.counts["clock"] = self.clock.now()
            return self._step()

    def _step(self) -> bool:
        if not self.queue and not self.active and not self.prefilling \
                and not self._queue.in_flight:
            return False
        self._shed_expired(self.clock.now())
        self._admit_ready()
        self.backend.drain_transfers()      # prefill hops: not decode traffic
        if self.prefilling:
            self._advance_prefill()
        if not self.active and not self._queue.in_flight:
            # nothing is decoding: skip the jitted decode step entirely — a
            # fixed-capacity step over all-garbage lanes would burn a full
            # model pass for nothing.  Only advance the clock (to the next
            # arrival) when no prefill is in flight either.
            if not self.prefilling and self.queue:
                self.clock.wait_until(self.queue[0].arrival)
            return self._next(True)
        results = self._pump_queue()
        if results is None:
            return self._next(True)
        for res in results:
            self._complete_round(res)
        return self._next(bool(self.queue or self.active or self.prefilling
                               or self._queue.in_flight))

    def _next(self, more: bool) -> bool:
        """Stall guard: a live scheduler must change *something* every
        iteration — admit, prefill, decode, finish, preempt, or move the
        clock.  A signature frozen for thousands of iterations means a
        logic bug (or a pathological 100%-fault injector), and an explicit
        error beats an infinite loop."""
        if not more:
            return False
        sig = (len(self.queue), len(self.active), len(self.prefilling),
               len(self.finished), self._total_tokens, self._step_i,
               self.clock.now())
        if sig == self._last_sig:
            self._idle_iters += 1
            if self._idle_iters > 10_000:
                raise RuntimeError(
                    "scheduler stalled: no progress in 10000 iterations "
                    f"(queue={len(self.queue)} active={len(self.active)} "
                    f"prefilling={len(self.prefilling)})")
        else:
            self._idle_iters = 0
            self._last_sig = sig
        return True

    def run(self, requests=None) -> ServingReport:
        """Drive until every submitted request has finished."""
        t0 = self.clock.now()
        if requests is not None:
            self.submit(requests)
        while self.step():
            pass
        report = ServingReport(
            metrics=sorted(self.finished, key=lambda m: m.rid),
            steps=self.step_log, wall_time=self.clock.now() - t0)
        self.finished, self.step_log = [], []
        self._step_i = 0
        self._rids = set()
        self._last_sig, self._idle_iters = None, 0
        return report


def serve(backend: DecodeBackend, requests: Sequence[Request],
          clock=None) -> ServingReport:
    """One-shot convenience wrapper: schedule ``requests`` to completion."""
    return Scheduler(backend, clock=clock).run(requests)


# ---------------------------------------------------------------------------
# disaggregated prefill/decode pools (DESIGN.md §14)
# ---------------------------------------------------------------------------


class _ShiftedClock:
    """The decode pool's view of time when both pools run in ONE process:
    ``now()`` is the base clock minus every second the prefill pool has spent
    computing and shipping pages so far.  On real disaggregated hardware
    those seconds overlap the decode pool's work; subtracting them is what
    makes the in-process measurement honest — decode-side TTFT/TPOT read as
    if the prefill pool were a separate machine.  With a ``VirtualClock``
    base the offset stays 0 (prefill passes take no virtual time) and the
    two timelines coincide exactly."""

    def __init__(self, base):
        self.base = base
        self.offset = 0.0

    def now(self) -> float:
        return self.base.now() - self.offset

    def wait_until(self, t: float) -> None:
        self.base.wait_until(t + self.offset)


@dataclasses.dataclass
class HandoffRecord:
    """One request's prefill→decode KV handoff, predicted next to measured.

    ``queue_s`` and ``prefill_s`` live on the BASE clock (the prefill pool's
    own timeline); ``submitted`` is the decode-clock instant the request
    joined the decode pool's queue — its rewritten arrival, from which every
    decode-side metric of this request is measured."""

    rid: int
    pages: int                # full prompt blocks shipped (kv_handoff_pages)
    bytes: int                # measured: device bytes landed by import_page
    predicted_bytes: float    # commodel.kv_handoff_ops closed form
    queue_s: float            # arrival → prefill start on the prefill pool
    prefill_s: float          # prefill-pool busy span (compute + page ship)
    submitted: float          # decode-clock arrival at the decode pool
    first_token: int          # prefill pool's final-position greedy token —
    #                           asserted equal to the decode pool's first
    #                           streamed token (cross-pool identity, §14)


@dataclasses.dataclass
class DisaggReport:
    """A disaggregated run: the decode pool's ServingReport (every
    per-request metric, on the decode clock) plus the handoff ledger.
    phase="handoff" StepRecords are interleaved into ``decode.steps``."""

    decode: ServingReport
    handoffs: List[HandoffRecord]
    wall_time: float          # base-clock span: prefill + ship + decode

    @property
    def metrics(self) -> List[RequestMetrics]:
        return self.decode.metrics

    @property
    def total_tokens(self) -> int:
        return self.decode.total_tokens

    def tokens_by_rid(self) -> Dict[int, List[int]]:
        return self.decode.tokens_by_rid()

    @property
    def handoff_pages(self) -> int:
        return sum(h.pages for h in self.handoffs)

    @property
    def handoff_bytes(self) -> int:
        return sum(h.bytes for h in self.handoffs)

    def summary(self) -> dict:
        out = self.decode.summary()
        queue = [h.queue_s for h in self.handoffs]
        out["disagg"] = {
            "handoffs": len(self.handoffs),
            "handoff_pages": self.handoff_pages,
            "handoff_bytes": self.handoff_bytes,
            "predicted_handoff_bytes": float(
                sum(h.predicted_bytes for h in self.handoffs)),
            "prefill_pool_busy_s": float(
                sum(h.prefill_s for h in self.handoffs)),
            "prefill_queue_p95_s": float(np.percentile(queue, 95))
            if queue else 0.0,
            "base_wall_time_s": self.wall_time,
        }
        return out


class DisaggScheduler:
    """Two engine pools, one serving plane (DESIGN.md §14).

    Fresh long-prompt admissions route to a *prefill pool* (monolithic
    batch-1 prefill, CP or TP layout); short prompts go straight to the
    *decode pool* (a full ``Scheduler`` over a paged, prefix-cached
    backend).  When the prefill pool finishes a prompt it hands the KV off:
    the prompt's full blocks ship page-by-page (``export_page`` →
    ``import_page``, measured device bytes), the pages are then pinned into
    the SHARED prefix index (``cache_prefix``), the prefill slot is freed,
    and the request is resubmitted to the decode pool — whose cache-aware
    admission (§13) hits on the freshly indexed blocks and prefills only
    the final partial page.  Head-of-line blocking dies here: a 2k-token
    prompt no longer stalls the decode pool's running batch for its whole
    prefill, only for one ≤ page_size suffix chunk.

    The handoff is a first-class *modeled* transfer: each one logs a
    phase="handoff" StepRecord whose predicted wire bytes
    (``commodel.kv_handoff_ops`` — pages × kv_page_bytes) are asserted
    EQUAL to the measured device bytes the import landed, per request.

    Invariants (asserted at runtime):

      * **Cross-pool token identity.**  The decode pool's first streamed
        token must equal the greedy token the prefill pool computed at the
        prompt's final position.  Greedy decode is deterministic, so this
        holds whenever both pools' prefill numerics agree bitwise — which
        they do when the pools share a layout kind (e.g. TP/TP).  A CP
        prefill pool's projection matmuls tile differently (~1e-7 KV noise,
        see tests/test_cp.py), giving token-level but not guaranteed-bitwise
        equality; the assertion is what surfaces a pairing that drifts.
      * **Handoff accounting.**  measured bytes == predicted bytes ==
        ``kv_handoff_pages(prompt_len, page_size)`` × page bytes, exactly.

    Timeline semantics: the decode pool runs on a ``_ShiftedClock`` that
    subtracts prefill-pool busy time from the base clock, so decode-side
    TTFT/TPOT measure the decode pool as dedicated hardware.  Handed-off
    requests' arrivals are rewritten to the handoff-completion instant on
    the decode clock (prefill-side latency lives in ``HandoffRecord``);
    deadlines therefore apply per pool — the prefill queue sheds against
    the original arrival, the decode pool against the rewritten one.
    """

    def __init__(self, prefill_backend: DecodeBackend,
                 decode_backend: DecodeBackend, clock=None,
                 chunk_size: int = None, admission: str = "conservative",
                 faults=None, route_prompt_len: Optional[int] = None,
                 retry_limit: int = 3, retry_backoff: float = 0.05):
        if not getattr(prefill_backend, "paged", False) \
                or not getattr(decode_backend, "paged", False):
            raise ValueError(
                "disaggregated pools hand KV off as pages — construct BOTH "
                "backends with paged=True")
        if decode_backend.prefix_index is None:
            raise ValueError(
                "the decode pool admits handed-off requests through its "
                "prefix index — construct the decode backend with "
                "prefix_cache=True (DESIGN.md §13/§14)")
        if prefill_backend.pool is not decode_backend.pool:
            raise ValueError(
                "disaggregated pools must share ONE KVPool (construct the "
                "prefill backend with pool=decode_backend.pool) — the "
                "handoff names pages of a common address space")
        pr = (prefill_backend._owner_base,
              prefill_backend._owner_base + prefill_backend.num_slots)
        dr = (decode_backend._owner_base,
              decode_backend._owner_base + decode_backend.num_slots)
        if max(pr[0], dr[0]) < min(pr[1], dr[1]):
            raise ValueError(
                f"pool-sharing backends need disjoint owner ranges, got "
                f"{pr} and {dr} — construct one with "
                f"owner_base=<the other's num_slots>")
        if prefill_backend.cfg is not decode_backend.cfg \
                and prefill_backend.cfg != decode_backend.cfg:
            raise ValueError(
                "both pools must serve the same model config — cross-pool "
                "token identity is asserted per request")
        self.prefill_backend = prefill_backend
        self.decode_backend = decode_backend
        # one index, both pools: the prefill pool INSERTS finished prompts
        # (and may evict cold entries under page pressure, §13), the decode
        # pool HITS on them at admission
        prefill_backend.prefix_index = decode_backend.prefix_index
        self.index = decode_backend.prefix_index
        self.base_clock = clock if clock is not None else WallClock()
        self._dclock = _ShiftedClock(self.base_clock)
        self.decode = Scheduler(decode_backend, clock=self._dclock,
                                chunk_size=chunk_size, admission=admission,
                                faults=faults, retry_limit=retry_limit,
                                retry_backoff=retry_backoff)
        ps = decode_backend.page_size
        self.route_prompt_len = (2 * ps if route_prompt_len is None
                                 else int(route_prompt_len))
        if self.route_prompt_len < ps:
            raise ValueError(
                f"route_prompt_len {self.route_prompt_len} < page_size "
                f"{ps}: a prompt with no full block has nothing to hand "
                f"off — the decode pool would cold-prefill it anyway")
        self.faults = faults
        self.retry_limit = int(retry_limit)
        self.retry_backoff = float(retry_backoff)
        self.pending: List[Request] = []      # prefill-pool queue, by arrival
        self.handoffs: List[HandoffRecord] = []
        self.finished_prefill: List[RequestMetrics] = []  # shed/errored here
        self._expected_first: Dict[int, int] = {}
        self._pre_retries: Dict[int, int] = {}
        self._rids: set = set()
        self._b = jnp.dtype(decode_backend.cfg.dtype).itemsize

    # ------------------------------------------------------------- intake
    def submit(self, requests) -> None:
        """Route: prompts of ``route_prompt_len``+ tokens queue for the
        prefill pool; everything else goes straight to the decode pool
        (its own ``submit`` validates capacity).  Prefill-routed requests
        are checked against BOTH pools now, so a request that could never
        fit fails at submit, not mid-run at handoff."""
        reqs = [requests] if isinstance(requests, Request) else list(requests)
        routed: List[Request] = []
        for r in reqs:
            if r.rid in self._rids:
                raise ValueError(
                    f"duplicate rid {r.rid}: already submitted this run")
            self._rids.add(r.rid)
            if r.prompt_len >= self.route_prompt_len:
                routed.append(r)
            else:
                self.decode.submit(r)
        pb, db = self.prefill_backend, self.decode_backend
        usable = db.pool.num_pages - 1          # minus the scratch page
        for r in routed:
            pre_len = pb._alloc_len(r.prompt_len)
            if pre_len > pb.max_len:
                raise ValueError(
                    f"request {r.rid} prompt ({pre_len} CP-padded "
                    f"positions) > prefill pool max_len {pb.max_len}")
            need = r.prompt_len + r.max_new_tokens - 1
            if need > db.max_len:
                raise ValueError(
                    f"request {r.rid} needs {need} cache positions > "
                    f"decode pool max_len {db.max_len}")
            need_pages = max(-(-pre_len // pb.page_size),
                             -(-need // db.page_size))
            if need_pages > usable:
                raise ValueError(
                    f"request {r.rid} needs {need_pages} pages > shared "
                    f"pool capacity {usable}")
            bisect.insort(self.pending, r, key=lambda x: x.arrival)

    def cancel(self, rid: int) -> bool:
        """Cancel wherever the request lives — the prefill-pool queue or
        anywhere inside the decode pool."""
        for req in self.pending:
            if req.rid == rid:
                self.pending.remove(req)
                self._fail(req, "cancelled")
                return True
        return self.decode.cancel(rid)

    # ------------------------------------------------------------- faults
    def _apply_fault(self, site: str) -> None:
        """Prefill-pool fault draws run on the BASE clock: a delay here
        stretches the prefill pool's timeline (and so the clock offset),
        never the decode pool's."""
        if self.faults is None:
            return
        f = self.faults.draw(site)
        if f is None:
            return
        if f.kind == "delay":
            self.base_clock.wait_until(self.base_clock.now() + f.delay_s)
        elif f.kind == "oom":
            raise MemoryError(f"injected fault at {site}")
        elif f.kind == "transient":
            raise TransientFault(f"injected fault at {site}")
        else:
            raise PermanentFault(f"injected fault at {site}")

    def _backoff(self, attempt: int) -> None:
        self.base_clock.wait_until(
            self.base_clock.now()
            + self.retry_backoff * 2.0 ** (attempt - 1))

    def _fail(self, req: Request, reason: str) -> None:
        """Finish a request on the prefill side (shed / cancelled /
        errored before handoff) — its metrics row joins the final report
        with no tokens, timeline on the base clock."""
        m = RequestMetrics(rid=req.rid, prompt_len=req.prompt_len,
                           arrival=req.arrival)
        m.retries = self._pre_retries.pop(req.rid, 0)
        m.finished = self.base_clock.now()
        m.finish_reason = reason
        self.finished_prefill.append(m)

    def _shed_pending(self, now: float) -> None:
        for req in [r for r in self.pending
                    if Scheduler._expired(r, now, True)]:
            self.pending.remove(req)
            self._fail(req, "deadline")

    # ------------------------------------------------------------- handoff
    def _prefill_arrived(self, now: float) -> bool:
        """Prefill + hand off every pending request whose arrival has
        passed, in arrival order.  Returns True if anything happened."""
        did = False
        while self.pending and self.pending[0].arrival <= now:
            if not self._prefill_one(self.pending[0]):
                break                     # deferred on pool pressure
            did = True
            now = self.base_clock.now()
            self._shed_pending(now)       # the pass moved the clock
        return did

    def _prefill_one(self, req: Request) -> bool:
        """One monolithic prefill + page handoff.  Returns False when the
        request was deferred (shared pool exhausted while the decode pool
        still holds pages — retried next iteration), True otherwise."""
        be = self.prefill_backend
        t_start = self.base_clock.now()
        try:
            # slot 0: the prefill pool runs one batch-1 pass at a time
            be.begin_prefill(0, req.prompt_len, 1)
        except MemoryError:
            # pool exhausted and the index drained of cold entries: wait
            # for the decode pool to free pages — unless it is idle too,
            # in which case the pages simply don't exist
            if (self.decode.active or self.decode.prefilling
                    or self.decode._queue.in_flight):
                return False
            self.pending.remove(req)
            self._fail(req, "error")
            return True
        self.pending.remove(req)
        queue_s = max(0.0, t_start - req.arrival)
        attempt = 0
        while True:
            try:
                self._apply_fault("prefill")
                tok = be.prefill_whole(0, req.prompt)
                break
            except (TransientFault, MemoryError):
                # injected prefill oom retries too: the slot's pages are
                # already claimed, and re-prefilling them is idempotent
                attempt += 1
                if attempt > self.retry_limit:
                    be.free_slots([0])
                    self._fail(req, "error")
                    return True
                self._pre_retries[req.rid] = \
                    self._pre_retries.get(req.rid, 0) + 1
                self._backoff(attempt)
            except PermanentFault:
                be.free_slots([0])
                self._fail(req, "error")
                return True
        # ship the prompt's full blocks BEFORE indexing them: an index
        # entry must never name a page whose decode-side content has not
        # landed — a hit on it would silently decode over garbage KV
        n_pages = kv_handoff_pages(req.prompt_len, be.page_size)
        table = [int(p) for p in
                 be.pool.block_table(be._owner(0))[:n_pages]]
        measured, shipped, attempt = 0, 0, 0
        while shipped < len(table):
            try:
                self._apply_fault("handoff")
                pg = table[shipped]
                measured += self.decode_backend.import_page(
                    pg, be.export_page(pg))
                shipped += 1
            except TransientFault:
                attempt += 1
                if attempt > self.retry_limit:
                    be.free_slots([0])
                    self._fail(req, "error")
                    return True
                self._pre_retries[req.rid] = \
                    self._pre_retries.get(req.rid, 0) + 1
                self._backoff(attempt)
            except PermanentFault:
                be.free_slots([0])
                self._fail(req, "error")
                return True
        be.cache_prefix(0, req.prompt)        # pins the shipped pages
        hit = self.index.lookup(np.asarray(req.prompt, np.int32))
        # §13 caps a lookup one position short, so a block-aligned prompt
        # hits one block fewer than it shipped — the last shipped page
        # stays pinned for FUTURE prompts sharing the prefix
        if list(hit.pages) != table[:len(hit.pages)] \
                or len(hit.pages) < n_pages - 1:
            raise RuntimeError(
                f"handoff pages diverged for rid {req.rid}: shipped "
                f"{table}, index holds {list(hit.pages)}")
        be.free_slots([0])
        elapsed = self.base_clock.now() - t_start
        # dedicated-hardware semantics: the decode clock does not see the
        # prefill pool's busy span
        self._dclock.offset += elapsed
        submitted = self._dclock.now()
        ops = kv_handoff_ops(be.cfg, n_pages, be.page_size, b=self._b)
        predicted = sum(o.wire_bytes for o in ops)
        if measured != int(predicted):
            raise RuntimeError(
                f"handoff bytes diverged for rid {req.rid}: measured "
                f"{measured} != predicted {int(predicted)} "
                f"({n_pages} pages × kv_page_bytes)")
        self.decode.step_log.append(StepRecord(
            step=self.decode._step_i, n_active=len(self.decode.active),
            collective_counts=Scheduler._count(ops),
            predicted_wire_bytes=predicted,
            measured_transfers={"count": n_pages, "bytes": measured},
            phase="handoff", rid=req.rid,
            prefix_len=n_pages * be.page_size, wall_s=elapsed))
        self.handoffs.append(HandoffRecord(
            rid=req.rid, pages=n_pages, bytes=measured,
            predicted_bytes=predicted, queue_s=queue_s, prefill_s=elapsed,
            submitted=submitted, first_token=int(tok)))
        self._expected_first[req.rid] = int(tok)
        self.decode.submit(dataclasses.replace(req, arrival=submitted))
        return True

    # ------------------------------------------------------------- driving
    def run(self, requests=None) -> DisaggReport:
        """Drive both pools until every submitted request has finished."""
        t0 = self.base_clock.now()
        d0 = self._dclock.now()
        if requests is not None:
            self.submit(requests)
        while True:
            now = self.base_clock.now()
            self._shed_pending(now)
            progressed = self._prefill_arrived(now)
            decode_idle = (not self.decode.active
                           and not self.decode.prefilling
                           and not self.decode._queue.in_flight)
            if decode_idle and self.pending and not progressed:
                # the decode pool would nap until ITS next arrival; if the
                # prefill pool's next request is due sooner on the base
                # clock, advance to it instead of letting a due handoff
                # wait behind the nap
                next_dec = (self.decode.queue[0].arrival
                            + self._dclock.offset
                            if self.decode.queue else float("inf"))
                if self.pending[0].arrival <= next_dec:
                    self.base_clock.wait_until(self.pending[0].arrival)
                    continue
            alive = self.decode.step()
            if not alive and not self.pending:
                break
        # fold prefill-side retries into the decode-side metrics rows
        metrics = sorted(self.decode.finished + self.finished_prefill,
                         key=lambda m: m.rid)
        for m in metrics:
            m.retries += self._pre_retries.pop(m.rid, 0)
        # the §14 cross-pool identity: greedy decode is deterministic, so
        # any divergence means the handed-off KV pages are not the pages
        # the decode pool would have written itself
        for m in metrics:
            exp = self._expected_first.get(m.rid)
            if exp is not None and m.tokens and m.tokens[0] != exp:
                raise RuntimeError(
                    f"cross-pool token divergence for rid {m.rid}: decode "
                    f"pool streamed {m.tokens[0]}, prefill pool computed "
                    f"{exp} — handed-off KV differs from native prefill")
        dec = ServingReport(metrics=metrics, steps=self.decode.step_log,
                            wall_time=self._dclock.now() - d0)
        report = DisaggReport(decode=dec, handoffs=self.handoffs,
                              wall_time=self.base_clock.now() - t0)
        self.decode.finished, self.decode.step_log = [], []
        self.decode._step_i = 0
        self.decode._rids = set()
        self.decode._last_sig, self.decode._idle_iters = None, 0
        self.handoffs, self.finished_prefill = [], []
        self._expected_first, self._pre_retries = {}, {}
        self._rids = set()
        return report
