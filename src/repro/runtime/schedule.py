"""Instruction queues: dynamic pipeline schedules that fill the PP decode
bubble (DESIGN.md §11).

The serving scheduler no longer calls ``backend.decode_step`` directly.
Every backend exposes ``make_queue()`` returning an *instruction queue* —
a small state machine the scheduler drains and refills:

- ``DynamicPPQueue`` (PPBackend): keeps up to ``backend.inflight``
  microbatch *groups* in flight.  Each group's decode round is a linear
  chain ``StageForward(0) → BoundarySend/Recv(0→1) → … → StageForward(p-1)
  → SampleToken``; the queue issues at most one compute instruction per
  stage per *tick*, picking the deepest stage first and, within a stage,
  the oldest round.  With ``depth`` groups resident the per-stage busy
  fraction approaches ``depth/p`` of a lockstep wave's reciprocal — the
  bubble-occupancy term ``commodel.pp_schedule_stats`` predicts in closed
  form and the pp-occupancy bench series measures.
- ``FusedQueue`` (ModelBackend/TPBackend, and any duck-typed backend
  without ``make_queue``): the fused ``decode_step`` wrapped as a
  degenerate 1-instruction queue so the scheduler protocol stays unified.

Deadlock freedom: a round's only ready instruction is the head of its
chain, heads of distinct rounds never alias a resource (each group owns
its caches/pages; boundary buffers are per-round), and the tick loop
always runs every ready head whose stage is free — so every in-flight
microbatch makes progress every ``p`` ticks and ``pump`` terminates.

This module is backend-agnostic on purpose: it duck-types against the
``start_round`` / ``run_stage`` / ``send_boundary`` / ``decode_step``
surface and never imports ``runtime.backends`` (which imports us).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Set

import numpy as np

from repro.runtime.tracing import span

__all__ = ["RoundResult", "DynamicPPQueue", "FusedQueue", "make_queue"]


# ---------------------------------------------------------------------------
# instruction set — the dynamic queue counts what it executed, per
# (kind, stage), so tests can pin it against commodel.pp_schedule_stats:
#
#   ("StageForward", s)     stage s's jitted fn ran for one group
#   ("BoundarySend", s)     a group's boundary pair shipped off stage s
#   ("BoundaryRecv", s)     ... and landed on stage s
#   ("SampleToken", None)   the last stage's logits greedy-sampled
#   ("PrefillChunk", None)  a prefill pass advanced (noted by the scheduler;
#                           prefill itself stays on the fused backend path)
#   ("Sync", None)          in-flight rounds drained before a cache/page
#                           mutation (admission prefill, chunk, realloc)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoundResult:
    """One completed decode round: ``tokens[i]`` belongs to ``slots[i]``.

    ``ticks``/``stage_busy``/``stage_idle`` are deltas of the queue's
    schedule clock since the previous completion, so summing them over a
    serving run reproduces the queue totals exactly.  ``transfers`` counts
    only this round's own boundary hops (attributed at send time).
    """
    mb: int
    slots: List[int]
    tokens: np.ndarray
    transfers: Dict[str, int]
    wall_s: float
    ticks: int
    stage_busy: List[int]
    stage_idle: List[int]


@dataclasses.dataclass
class _Round:
    """An in-flight decode round: a linear chain whose head is the next
    ``StageForward``; ``x`` is the activation (token ids at stage 0)."""
    mb: int
    seq: int
    slots: List[int]
    x: object
    pos: object
    bt: object
    live: int
    stage: int = 0
    tr_count: int = 0
    tr_bytes: int = 0


# ---------------------------------------------------------------------------
# dynamic PP queue
# ---------------------------------------------------------------------------


class DynamicPPQueue:
    """Priority-driven dynamic schedule over a PPBackend's stage fns.

    Tick loop (one tick = one sweep over stages, deepest first):

    1. For each stage ``s`` from ``p-1`` down to ``0``, issue the oldest
       round whose head targets ``s`` (at most one per stage per tick) —
       dispatches are async, so on a parallel host the per-tick stage
       work overlaps; the deterministic tick count is what the closed
       form ``commodel.pp_schedule_stats`` pins either way.
    2. Tick tail: move every just-produced boundary to its next stage
       (logged TransferRecords are attributed to the owning round) and
       force ``SampleToken`` on rounds that cleared the last stage.

    Deepest-first ordering is what makes the schedule drain-first: a
    round near completion never waits behind a newly started one, so
    with ``depth`` ≥ ``p`` every stage is busy every tick once the
    pipeline fills.
    """

    def __init__(self, backend):
        self.backend = backend
        self.p = int(backend.p)
        self.depth = int(backend.inflight)
        self.group_size = int(backend.group_size)
        self._rounds: List[_Round] = []
        self._seq = 0
        self.ticks = 0
        self.busy = [0] * self.p
        self.idle = [0] * self.p
        self.counts: collections.Counter = collections.Counter()
        self._mark = (0, [0] * self.p, [0] * self.p)

    # -- state ------------------------------------------------------------

    @property
    def in_flight(self) -> int:
        return len(self._rounds)

    def busy_groups(self) -> Set[int]:
        """Groups with issued work in flight — their caches/pages must not
        be freed or reallocated (preemption picks victims elsewhere)."""
        return {r.mb for r in self._rounds}

    def pending_groups(self) -> Set[int]:
        """Groups that must not get another ``begin_round``."""
        return {r.mb for r in self._rounds}

    # -- refill -----------------------------------------------------------

    def begin_round(self, g: int, tokens: np.ndarray, pos: np.ndarray,
                    live: int):
        """Start one decode round for group ``g`` (``live`` of its rows
        decoding) from the scheduler's per-slot token/pos state.  Page
        extends happen here, before any instruction is issued, so pool
        exhaustion (MemoryError) surfaces with the failed group *not* in
        flight."""
        if any(r.mb == g for r in self._rounds):
            raise RuntimeError(f"group {g} already has a round in flight")
        x, pos_g, bt = self.backend.start_round(g, tokens, pos)
        self._seq += 1
        lo = g * self.group_size
        self._rounds.append(_Round(
            mb=g, seq=self._seq, slots=list(range(lo, lo + self.group_size)),
            x=x, pos=pos_g, bt=bt, live=live))

    # -- drain ------------------------------------------------------------

    def pump(self) -> List[RoundResult]:
        """Tick until at least one round completes; return the completions
        (empty only when nothing is in flight).  The pump's span is split
        evenly over the rounds it completed, as their ``wall_s``."""
        if not self._rounds:
            return []
        done: List[RoundResult] = []
        with span("sched.round",
                  live=sum(r.live for r in self._rounds)) as sp:
            while not done:
                done = self._tick()
        wall = sp.seconds / len(done)
        for res in done:
            res.wall_s = wall
        return done

    def sync(self) -> List[RoundResult]:
        """Drain every in-flight round (the ``Sync`` instruction): called
        before any operation that writes caches/pages a round may read."""
        out: List[RoundResult] = []
        drained = bool(self._rounds)
        while self._rounds:
            out.extend(self.pump())
        if drained:
            self.counts["Sync", None] += 1
        return out

    def abort_all(self) -> None:
        """Drop all in-flight rounds without completing them (permanent
        fault: the active set is being error-finished anyway)."""
        self._rounds.clear()

    def note_prefill(self, slot: int) -> None:
        self.counts["PrefillChunk", None] += 1

    # -- internals --------------------------------------------------------

    def _tick(self) -> List[RoundResult]:
        self.ticks += 1
        ran = []
        for s in range(self.p - 1, -1, -1):
            cand = None
            for r in self._rounds:
                if r.stage == s and (cand is None or r.seq < cand.seq):
                    cand = r
            if cand is None:
                self.idle[s] += 1
                continue
            out = self.backend.run_stage(cand.mb, s, cand.x, cand.pos,
                                         cand.bt)
            self.busy[s] += 1
            self.counts["StageForward", s] += 1
            ran.append((cand, out))
        # tick tail: boundary moves + sample forcing happen after every
        # stage dispatch of the tick is in the air
        eng = self.backend.engine
        finished = []
        for r, out in ran:
            if r.stage < self.p - 1:
                n0 = len(eng.transfers)
                r.x = self.backend.send_boundary(out, r.stage)
                for rec in eng.transfers[n0:]:
                    r.tr_count += rec.count
                    r.tr_bytes += rec.bytes
                self.counts["BoundarySend", r.stage] += 1
                self.counts["BoundaryRecv", r.stage + 1] += 1
                r.stage += 1
            else:
                finished.append((r, out))
        results = []
        for r, logits in finished:
            self._rounds.remove(r)
            toks = self.backend._first_token(logits)
            self.counts["SampleToken", None] += 1
            results.append(self._result(r, toks))
        return results

    def _result(self, r: _Round, toks: np.ndarray) -> RoundResult:
        d_ticks = self.ticks - self._mark[0]
        d_busy = [b - m for b, m in zip(self.busy, self._mark[1])]
        d_idle = [i - m for i, m in zip(self.idle, self._mark[2])]
        self._mark = (self.ticks, list(self.busy), list(self.idle))
        return RoundResult(
            mb=r.mb, slots=r.slots, tokens=np.asarray(toks, np.int32),
            transfers={"count": r.tr_count, "bytes": r.tr_bytes},
            wall_s=0.0, ticks=d_ticks, stage_busy=d_busy, stage_idle=d_idle)


# ---------------------------------------------------------------------------
# degenerate fused queue
# ---------------------------------------------------------------------------


class FusedQueue:
    """The fused ``decode_step`` as a 1-instruction queue (group 0 spans
    every slot).  ``begin_round`` stores *references* to the scheduler's
    token/pos arrays: after a MemoryError-triggered preemption mutates
    them in place, the retried ``pump`` sees the updated state — bitwise
    the pre-refactor recovery ladder."""

    def __init__(self, backend):
        self.backend = backend
        self.p = 1
        self.depth = 1
        self.group_size = int(backend.num_slots)
        self._round = None
        self.ticks = 0
        self.busy = [0]
        self.idle = [0]

    @property
    def in_flight(self) -> int:
        return 0 if self._round is None else 1

    def busy_groups(self) -> Set[int]:
        # nothing is ever issued before pump returns, so a pending round
        # pins no pages: preemption may pick any victim (old behavior)
        return set()

    def pending_groups(self) -> Set[int]:
        return set() if self._round is None else {0}

    def begin_round(self, g: int, tokens: np.ndarray, pos: np.ndarray,
                    live: int):
        if self._round is not None:
            raise RuntimeError("fused queue already has a round pending")
        self._round = (g, tokens, pos, live)

    def pump(self) -> List[RoundResult]:
        if self._round is None:
            return []
        g, tokens, pos, live = self._round
        # may raise (faults, pool exhaustion): the round is retained so the
        # recovery ladder retries it against the mutated token/pos state
        with span("sched.round", live=live) as sp:
            nxt = self.backend.decode_step(tokens, pos)
        self._round = None
        self.ticks += 1
        self.busy[0] += 1
        tr = self.backend.drain_transfers()
        return [RoundResult(
            mb=g, slots=list(range(self.group_size)),
            tokens=np.asarray(nxt, np.int32), transfers=dict(tr),
            wall_s=sp.seconds, ticks=1, stage_busy=[1], stage_idle=[0])]

    def sync(self) -> List[RoundResult]:
        return self.pump()

    def abort_all(self) -> None:
        self._round = None

    def note_prefill(self, slot: int) -> None:
        pass


def make_queue(backend):
    """Build the instruction queue for ``backend``.

    Resolved via the backend *class*, not instance getattr: test harnesses
    wrap backends in ``__getattr__``-delegating proxies to count
    ``decode_step`` calls, and delegation would hand back a queue bound to
    the inner object, bypassing the proxy.  A class without ``make_queue``
    gets the degenerate fused queue around the outer object.
    """
    mk = getattr(type(backend), "make_queue", None)
    if mk is None:
        return FusedQueue(backend)
    return mk(backend)
