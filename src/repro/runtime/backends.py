"""DecodeBackend: one slot-based decode protocol over all three engines.

The repo grew three decode paths — the GSPMD ``Model`` path
(runtime/engine.py), the explicit-collective TP engine
(core/parallel_exec.tp_decode_step) and the per-stage-jit ``PipelineEngine``
— each serving one fixed, same-length batch with a scalar decode position.
The continuous-batching scheduler (runtime/scheduler.py) instead needs a
*slot* abstraction: a KV cache with ``num_slots`` independent batch rows,
where any row can be (re)filled by prefilling a new request while the other
rows keep decoding from their own depths.

The protocol (DESIGN.md §7):

  prefill_into_slots(prompts, slots) -> first greedy token per request.
      Each request is prefilled alone at its true length (batch-1 pass —
      row-wise math is identical to serving it solo, which is what makes the
      scheduler token-identical to isolated serving) and its seeded KV cache
      is scattered into the slot's batch row.
  decode_step(tokens [B], pos [B]) -> next greedy token for every slot.
      ONE jitted step over the full slot batch with per-sequence positions
      (models/transformer.py + core/parallel_exec.py vector-pos paths);
      free slots decode garbage that the scheduler ignores — the collective
      *count* of the step is batch-invariant either way (the paper's
      Tables III–VI carry no batch term in the count columns), which is why
      a fixed-capacity step can serve a varying active set.
  free_slots(slots)
      Bookkeeping only: a freed row is overwritten by the next admission.

Per-step predicted communication comes from ``commodel.comm_ops_for`` via
:meth:`DecodeBackend.decode_comm_ops`; the PP/hybrid backend additionally
exposes the engine's measured TransferRecords through ``drain_transfers``.

Paged mode (DESIGN.md §8).  With ``paged=True`` every backend swaps the
contiguous [.., num_slots, max_len, ..] slot cache for fixed-size KV *pages*
([.., num_pages, page_size, ..]) managed by a host-side ``runtime.kvpool.
KVPool``: slots own pages on demand instead of a pinned ``max_len`` row, so
long-context and short requests share one pool without reserving worst-case
memory.  Prefill becomes *chunked* — three extra methods drive it:

  begin_prefill(slot, prompt_len)   allocate the slot's pages
  prefill_chunk(slot, tokens, start) -> greedy token of the chunk's last
      position (only the final chunk's is meaningful); ONE jitted paged
      pass per chunk, same collective schedule as a full prefill pass
      (``commodel.chunked_prefill_ops``)
  finish_prefill(slot)              mark the slot decode-eligible

``decode_step`` keeps its protocol signature; in paged mode it extends each
decode-eligible slot's pages to cover the incoming position and points every
ineligible slot's block-table row at the reserved scratch page 0, so the
fixed-capacity step's garbage lanes can never corrupt a live page.

Context parallelism (``c > 1`` on the explicit backends, DESIGN.md §9)
changes ONLY how a request's prefill runs: the prompt is padded to a
multiple of c, sequence-sharded over the mesh's cp axis, and each layer's
K/V ring-exchanged (``parallel_exec.cp_prefill`` / the CP stage fns) — the
ring assembles the FULL cache on every cp worker, so the seeded KV drops
into the contiguous slot row via the ordinary ``_scatter``, or into the KV
pages via ``_seed_pages``, and ``decode_step`` is untouched (it runs
replicated over the cp axis).  CP and chunked prefill are alternative
long-prompt strategies: ``Scheduler(chunk_size=...)`` rejects c>1 backends.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config.base import ModelConfig
from repro.core import parallel_exec as px
from repro.core.commodel import DEFAULT_QUANT_CHUNK, CommOp, \
    chunked_prefill_ops, comm_ops_for
from repro.models.layers import paged_cache_update
from repro.models.transformer import get_model
from repro.runtime.kvpool import KVPool
from repro.runtime.prefix_index import PrefixIndex
from repro.runtime.schedule import DynamicPPQueue, FusedQueue
from repro.runtime.tracing import span


@runtime_checkable
class DecodeBackend(Protocol):
    """Slot-based decode interface the scheduler drives (DESIGN.md §7)."""

    cfg: ModelConfig
    num_slots: int
    max_len: int
    t: int
    c: int
    p: int
    inflight: int        # in-flight microbatch groups (1 on fused backends)
    group_size: int      # slots per group (num_slots // inflight)

    def prefill_into_slots(self, prompts: Sequence[np.ndarray],
                           slots: Sequence[int]) -> np.ndarray: ...

    def decode_step(self, tokens: np.ndarray,
                    pos: np.ndarray) -> np.ndarray: ...

    def free_slots(self, slots: Sequence[int]) -> None: ...

    def decode_comm_ops(self, batch: int = 1) -> List[CommOp]: ...

    def drain_transfers(self) -> dict: ...


def _write_slot(big, small, slot):
    """Scatter a batch-1 cache pytree into batch row ``slot`` of the slot
    cache (every cache family keeps batch on axis 1 of each leaf)."""
    return jax.tree.map(
        lambda b, s: jax.lax.dynamic_update_slice_in_dim(b, s, slot, axis=1),
        big, small)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_page(pools, rows, page):
    """Land one exported KV page's rows {k, v: [L, ps, kv, D]} at physical
    page ``page`` of the device page pools ([L, P, ps, kv, D] leaves, page
    axis 1) — the import half of the disaggregated KV handoff
    (DESIGN.md §14).  ``page`` is a traced scalar, so repeated imports
    compile once per pool shape, like ``_copy_page_rows``."""
    def one(a, d):
        return jax.lax.dynamic_update_slice_in_dim(a, d[:, None], page,
                                                   axis=1)
    return jax.tree.map(one, pools, rows)


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page_rows(pools, src, dst):
    """Replay one ``KVPool`` copy-on-write on the device page pools: copy
    physical page ``src``'s rows into page ``dst`` on every leaf (page axis
    is axis 1 of each [L, P, ps, kv, D] pool).  The whole page is copied —
    rows past the owner's committed length are garbage either way (the
    paged attention mask never exposes them, DESIGN.md §8) and the static
    shape keeps this ONE compiled module per pool shape.  src/dst are
    traced scalars, so repeated COWs never recompile."""
    def one(a):
        page = jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(a, page, dst, axis=1)
    return jax.tree.map(one, pools)


def _seed_pages(pools, small, bt):
    """Scatter a batch-1 contiguous cache {k,v: [L, 1, S, kv, D]} into the
    KV page pools {k,v: [L, P, ps, kv, D]} at the pages ``bt`` [1, n]
    names — the CP gather-into-pages handoff (DESIGN.md §9).  Pure data
    movement on unsharded axes (kv heads keep their TP sharding), jitted
    with the pools donated so the write happens in place."""
    pos = jnp.zeros((1,), jnp.int32)

    def per_layer(pk, pv, k, v):
        return paged_cache_update(pk, pv, k, v, pos, bt)

    ck, cv = jax.vmap(per_layer)(pools["k"], pools["v"],
                                 small["k"], small["v"])
    return {"k": ck, "v": cv}


class _BackendBase:
    """Shared slot bookkeeping + predicted per-step communication."""

    def __init__(self, cfg: ModelConfig, num_slots: int, max_len: int,
                 t: int, p: int, paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None, c: int = 1,
                 quant_collectives: Optional[str] = None,
                 quant_chunk: int = DEFAULT_QUANT_CHUNK,
                 prefix_cache: bool = False,
                 pool: Optional[KVPool] = None, owner_base: int = 0):
        if not cfg.is_decoder:
            raise ValueError(f"{cfg.name} is encoder-only: no decode")
        if quant_collectives is not None and paged:
            raise ValueError(
                "quantized collectives cover the contiguous decode step; "
                "the paged engines run full-width (DESIGN.md §12)")
        if prefix_cache and not paged:
            raise ValueError(
                "prefix caching shares KV pages across requests — "
                "construct the backend with paged=True (DESIGN.md §13)")
        if prefix_cache and c > 1:
            raise ValueError(
                "a cache hit prefills only the novel suffix, which needs "
                "the chunked (offset) prefill path; CP prefills the whole "
                "sequence monolithically (DESIGN.md §9/§13) — use c=1")
        self.cfg = cfg
        self.quant = quant_collectives
        self.quant_chunk = int(quant_chunk)
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.t, self.c, self.p = int(t), int(c), int(p)
        # fused backends run one microbatch group spanning every slot;
        # PPBackend overrides both when inflight > 1 (DESIGN.md §11)
        self.inflight = 1
        self.group_size = self.num_slots
        self.paged = bool(paged)
        if owner_base < 0:
            raise ValueError(
                f"owner_base must be >= 0 (negative ids belong to the "
                f"prefix index), got {owner_base}")
        self._owner_base = int(owner_base)
        if pool is not None and not paged:
            raise ValueError("a shared KVPool needs paged=True")
        if self.paged:
            if cfg.family != "dense":
                raise ValueError(
                    f"paged mode covers dense attention; {cfg.name} is "
                    f"{cfg.family}")
            if cfg.sliding_window:
                raise ValueError(
                    "paged mode keeps every position (no ring wrap); "
                    f"{cfg.name} uses a sliding window — serve it contiguous")
            self.page_size = int(page_size)
            self.pages_per_slot = -(-self.max_len // self.page_size)
            if pool is not None:
                # disaggregated pools (DESIGN.md §14) share ONE page space:
                # both backends' block tables name pages of the same host
                # allocator, so pages the prefill pool wrote are adoptable
                # by the decode pool; owner_base keeps their slot owner ids
                # disjoint.  NOTE: device page pools stay per-backend —
                # sharing the allocator shares *addressing*, the page
                # CONTENT still crosses via export_page/import_page.
                if pool.page_size != self.page_size:
                    raise ValueError(
                        f"shared pool page_size {pool.page_size} != "
                        f"backend page_size {self.page_size}")
                self.pool = pool
            else:
                if num_pages is None:
                    # capacity parity with the contiguous slot cache, +1 for
                    # the reserved scratch page; a smaller pool
                    # oversubscribes (long-context mixes that would OOM
                    # contiguous slots)
                    num_pages = 1 + self.num_slots * self.pages_per_slot
                self.pool = KVPool(num_pages, self.page_size)
            self.block_tables = np.zeros(
                (self.num_slots, self.pages_per_slot), np.int32)
            self._decodable: set = set()
            self._worst: dict = {}      # slot -> worst-case pages committed
        self.prefix_index = PrefixIndex(self.pool) if prefix_cache else None

    # -- paged bookkeeping (DESIGN.md §8) ----------------------------------
    def _require_paged(self):
        if not self.paged:
            raise RuntimeError("chunked-prefill API needs paged=True")

    def _owner(self, slot: int) -> int:
        """Pool owner id of a local slot.  Backends sharing one KVPool
        (disaggregated pools, DESIGN.md §14) claim disjoint owner ranges
        via ``owner_base``; single-pool backends keep owner == slot."""
        return self._owner_base + slot

    def _set_table(self, slot: int) -> None:
        table = self.pool.block_table(self._owner(slot))
        row = np.zeros(self.pages_per_slot, np.int32)
        row[:len(table)] = table
        self.block_tables[slot] = row

    def _pages_for(self, tokens: int) -> int:
        return -(-tokens // self.page_size)

    def _alloc_len(self, prompt_len: int) -> int:
        """Cache positions a prompt claims up front: its true length, or the
        CP-padded length (prompts pad to a multiple of c so the sequence
        shards equally — the pad rows' garbage KV sits inside the slot's
        own pages and decode overwrites each position before the causal
        mask ever exposes it, DESIGN.md §9)."""
        return prompt_len if self.c == 1 else \
            -(-prompt_len // self.c) * self.c

    def can_admit(self, prompt_len: int, max_new_tokens: int = 1,
                  optimistic: bool = False) -> bool:
        """True when the pool can cover this request's WORST case (prompt +
        max_new_tokens - 1 positions, or the CP-padded prompt if longer) on
        top of every live request's committed future growth.  Without
        preemption (DESIGN.md §7/8) this admission gate is what keeps an
        oversubscribed pool from running out of pages mid-decode: a request
        the gate rejects stays queued until evictions free pages.

        ``optimistic=True`` (DESIGN.md §10) gates only on the request's
        CURRENT need — the pages its prompt/prefix claims at ``begin_prefill``
        — ignoring everyone's future decode growth.  Mid-decode page
        exhaustion then becomes possible and is the scheduler's problem
        (preemption-by-recompute); the payoff is that EOS-heavy traffic no
        longer strands pool capacity on decode budgets that never
        materialize.

        With a prefix index attached, pages pinned only by unreferenced
        cached prefixes count as free — they are reclaimable on demand
        (``_claim_guard``), so a pool full of cold cache never deadlocks
        admission (DESIGN.md §13)."""
        self._require_paged()
        free = self.pool.free_pages + (self.prefix_index.reclaimable_pages()
                                       if self.prefix_index else 0)
        if optimistic:
            return free >= self._pages_for(self._alloc_len(prompt_len))
        # committed growth of THIS backend's own slots (index owners never
        # grow — negative ids — and a pool-sharing sibling backend tracks
        # its own commitments: its live pages are already out of ``free``,
        # and its future growth is recovered by preemption, not reserved
        # across the pool boundary)
        committed = sum(
            max(0, self._worst.get(o - self._owner_base, 0)
                - len(self.pool.block_table(o)))
            for o in self.pool.owners()
            if 0 <= o - self._owner_base < self.num_slots)
        need = self._pages_for(max(self._alloc_len(prompt_len),
                                   prompt_len + max_new_tokens - 1))
        return free - committed >= need

    def _claim_guard(self, fn):
        """Run a pool claim; under pressure, evict LRU cached prefixes
        until it succeeds (or the index is drained — then the MemoryError
        propagates to the scheduler's preemption ladder)."""
        while True:
            try:
                return fn()
            except MemoryError:
                if self.prefix_index is None \
                        or not self.prefix_index.evict_one():
                    raise

    def _apply_cow(self) -> None:
        """Replay the pool's pending copy-on-write events as device page
        copies — MUST run after every ``pool.extend`` before the next pass
        touches the privatized page (DESIGN.md §13)."""
        for ev in self.pool.take_cow_events():
            self._copy_page(ev.src, ev.dst)

    def _copy_page(self, src: int, dst: int) -> None:
        """Copy physical page src -> dst on this backend's device pools."""
        raise NotImplementedError

    # -- KV-page handoff (disaggregated pools, DESIGN.md §14) --------------
    def export_page(self, page: int) -> dict:
        """Read physical ``page``'s KV rows off this backend's device page
        pools as host arrays {k, v: [L, ps, kv, D]} — the unit the
        disaggregated prefill→decode handoff ships
        (``commodel.kv_handoff_ops``)."""
        self._require_paged()
        return {key: np.asarray(self.cache[key][:, page])
                for key in ("k", "v")}

    def import_page(self, page: int, data: dict) -> int:
        """Land exported KV rows {k, v: [L, ps, kv, D]} at physical
        ``page`` of this backend's device page pools; returns the device
        bytes written — the measured half of the handoff invariant
        (asserted equal to ``kv_handoff_ops``'s closed form per request)."""
        self._require_paged()
        rows = {key: jnp.asarray(np.asarray(data[key]),
                                 jnp.dtype(self.cfg.dtype))
                for key in ("k", "v")}
        self.cache = _write_page(self.cache, rows, jnp.int32(page))
        return sum(int(a.nbytes) for a in rows.values())

    def begin_prefill(self, slot: int, prompt_len: int,
                      max_new_tokens: int = 1) -> None:
        """Allocate the slot's pages for a new request's prompt (CP-padded
        when c > 1) and commit its worst-case decode growth
        (see ``can_admit``)."""
        self._require_paged()
        self.pool.free(self._owner(slot))   # defensive: slot may be reused
        self._decodable.discard(slot)
        self._claim_guard(
            lambda: self.pool.allocate(self._owner(slot),
                                       self._alloc_len(prompt_len)))
        self._worst[slot] = self._pages_for(
            max(self._alloc_len(prompt_len),
                prompt_len + max_new_tokens - 1))
        self._set_table(slot)

    def begin_prefill_cached(self, slot: int, prompt,
                             max_new_tokens: int = 1) -> int:
        """Cache-aware admission (DESIGN.md §13): look the prompt up in the
        prefix index, adopt the longest cached prefix's pages into the
        slot, and extend to the full prompt — claiming fresh pages for the
        suffix and copy-on-writing a partially shared tail (a fully cached
        prompt is capped one position short, so its last page IS shared
        partially and privatizes here, before the suffix chunk writes it).
        Returns the hit length in tokens (0 = cold: plain begin_prefill).
        The caller prefills only positions hit..prompt_len-1."""
        self._require_paged()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.prefix_index is None:
            self.begin_prefill(slot, len(prompt), max_new_tokens)
            return 0
        self.pool.free(self._owner(slot))   # defensive: slot may be reused
        self._decodable.discard(slot)
        hit = self.prefix_index.lookup(prompt)
        if not hit.hit:
            self.begin_prefill(slot, len(prompt), max_new_tokens)
            return 0
        self.pool.adopt(self._owner(slot), hit.pages, hit.length)
        try:
            self._claim_guard(
                lambda: self.pool.extend(self._owner(slot),
                                         self._alloc_len(len(prompt))))
        except MemoryError:
            # nothing half-claimed: extend is atomic
            self.pool.free(self._owner(slot))
            raise
        self._apply_cow()
        self._worst[slot] = self._pages_for(
            max(self._alloc_len(len(prompt)),
                len(prompt) + max_new_tokens - 1))
        self._set_table(slot)
        return hit.length

    def cache_prefix(self, slot: int, tokens) -> int:
        """Insert a fully prefilled slot's prompt blocks into the prefix
        index (no-op without one); returns new entries created.  Only full
        blocks are indexed, and they are exactly the slot's first pages —
        committed by the prefill that just finished, never rewritten (decode
        writes land at positions past the prompt)."""
        if not self.paged or self.prefix_index is None:
            return 0
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        return self.prefix_index.insert(
            tokens, self.pool.block_table(self._owner(slot)))

    def prefill_chunk(self, slot: int, tokens, start: int) -> int:
        """One chunked-prefill pass for ``tokens`` at positions
        start..start+S-1; returns the greedy token of the chunk's last
        position (the request's first token when this is the final chunk)."""
        logits = self.prefill_chunk_logits(slot, tokens, start)
        with span("backend.argmax"):
            return int(np.argmax(logits))

    def prefill_chunk_logits(self, slot: int, tokens,
                             start: int) -> np.ndarray:
        """``prefill_chunk``'s pass, returning the chunk's last-position
        logits [v] instead of their argmax — what a comparison against a
        reference forward reads."""
        self._require_paged()
        if self.c > 1:
            raise RuntimeError(
                "chunked prefill and context parallelism are alternative "
                "long-prompt strategies; a c>1 backend prefills "
                "monolithically via prefill_whole (DESIGN.md §9)")
        chunk = np.asarray(tokens, np.int32)[None, :]
        pos = np.asarray([start], np.int32)
        bt = self.block_tables[slot:slot + 1]
        return self._paged_logits(chunk, pos, bt, phase="prefill")[0]

    def prefill_whole(self, slot: int, tokens, start: int = 0) -> int:
        """Monolithic prefill of one request into its allocated pages:
        one maximal chunk at c == 1, or — under context parallelism — one
        sequence-sharded CP pass whose assembled full KV is scattered into
        the slot's pages (``_seed_pages``).  Returns the first greedy
        token; ``begin_prefill`` (or ``begin_prefill_cached``, whose hit
        length becomes ``start``) must have run.  With ``start > 0`` only
        positions start.. are computed — ONE suffix chunk over the cached
        prefix's pages (DESIGN.md §13)."""
        self._require_paged()
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if not 0 <= start < len(tokens):
            raise ValueError(
                f"start {start} outside [0, {len(tokens)}) — a cache hit "
                "always leaves at least the final position to prefill")
        if self.c == 1:
            return self.prefill_chunk(slot, tokens[start:], start)
        if start:
            raise RuntimeError(
                "suffix prefill needs the chunked (offset) path; "
                "c > 1 backends prefill monolithically (DESIGN.md §9)")
        logits, small = self._prefill_one(tokens)
        self._seed_slot_pages(small, slot)
        return int(np.argmax(np.asarray(logits)[0]))

    def _seed_slot_pages(self, small, slot: int) -> None:
        """Write a batch-1 contiguous cache into the slot's pages."""
        raise NotImplementedError

    def finish_prefill(self, slot: int) -> None:
        """Mark a fully-prefilled slot decode-eligible."""
        self._require_paged()
        self._decodable.add(slot)

    def _paged_decode(self, tokens, pos) -> np.ndarray:
        """Paged decode step: extend decode-eligible slots' pages to cover
        the incoming position, then ONE jitted paged pass (S=1) over the
        full slot batch.  Ineligible slots' block-table rows are pointed at
        the scratch page so their garbage lanes stay harmless."""
        pos = np.asarray(pos)
        with span("backend.pages") as sp:
            claimed = self.pool.pages_claimed
            for slot in sorted(self._decodable):
                self._claim_guard(
                    lambda s=slot: self.pool.extend(self._owner(s),
                                                    int(pos[s]) + 1))
                self._set_table(slot)
            self._apply_cow()
            bt = self.block_tables.copy()
            for slot in range(self.num_slots):
                if slot not in self._decodable:
                    bt[slot] = 0                # scratch page (kvpool.py)
            sp.counts["pages"] = self.pool.pages_claimed - claimed
        logits = self._paged_logits(
            np.asarray(tokens, np.int32)[:, None],
            np.asarray(pos, np.int32), bt, phase="decode")
        with span("backend.argmax"):
            return np.asarray(np.argmax(logits, -1), np.int32)

    def _paged_logits(self, tokens, pos, bt, phase: str) -> np.ndarray:
        """Host logits [B, v] of one paged pass, each part under its span:
        dispatch (inputs to the device, the jitted call's enqueue), wait
        (the device step), fetch (the logits' copy to the host).  The copy
        is queued before the wait, so the runtime starts it as soon as the
        step is done, not after the wait has returned to Python."""
        with span("backend.dispatch"):
            logits = self._paged_call(tokens, pos, bt, phase)
        with span("backend.wait"):
            logits.copy_to_host_async()
            jax.block_until_ready(logits)
        with span("backend.fetch") as sp:
            host = np.asarray(logits)
            sp.counts["bytes"] = host.nbytes
        return host

    def _paged_call(self, tokens, pos, bt, phase: str):
        """Device logits [B, v] of one paged pass; updates the cache in
        place."""
        raise NotImplementedError

    def chunk_comm_ops(self, chunk_len: int, batch: int = 1) -> List[CommOp]:
        """Predicted collectives for ONE prefill chunk of ``chunk_len``
        tokens — the per-chunk rows of ``commodel.chunked_prefill_ops`` at
        the backend's activation width.  Counts are chunk-length- and
        batch-invariant; only message bytes scale."""
        return chunked_prefill_ops(
            self.cfg, chunk_len, chunk_len, self.t, self.p, batch=batch,
            b=jnp.dtype(self.cfg.dtype).itemsize, gather_mode="allgather")

    def decode_comm_ops(self, batch: int = 1) -> List[CommOp]:
        """Predicted collectives for ONE decode step over ``batch`` rows:
        the decode-phase rows of ``comm_ops_for`` at s_d=2 (one step past
        the prefill token), gather_mode="allgather" (the XLA engines), at
        the backend's actual activation width — so predicted bytes sit on
        the same scale as the measured TransferRecords.  Independent of c:
        context parallelism is prefill-only (DESIGN.md §9).  A
        quant-collectives backend gets the decomposed rows (f32 amax
        allreduce + 1-byte reducescatter/allgather per layer AR,
        DESIGN.md §12) — what its compiled decode module actually shows."""
        ops = comm_ops_for(self.cfg, 1, 2, self.t, self.p, c=self.c,
                           batch=batch,
                           b=jnp.dtype(self.cfg.dtype).itemsize,
                           gather_mode="allgather",
                           quant=self.quant, quant_chunk=self.quant_chunk)
        return [o for o in ops if o.phase == "decode"]

    def prefill_comm_ops(self, prompt_len: int,
                         batch: int = 1) -> List[CommOp]:
        """Predicted collectives for ONE monolithic prefill pass of a
        ``prompt_len``-token prompt at the backend's (t, c, p) layout —
        under CP this carries the per-layer ring rows of
        ``commodel.cp_comm_ops`` plus the TP/PP rows at the padded
        ceil(prompt_len/c) shard each rank processes."""
        ops = comm_ops_for(self.cfg, prompt_len, 1, self.t, self.p,
                           c=self.c, batch=batch,
                           b=jnp.dtype(self.cfg.dtype).itemsize,
                           gather_mode="allgather")
        return [o for o in ops if o.phase == "prefill"]

    def drain_transfers(self) -> dict:
        """Inter-stage bytes moved since the last drain (PP only)."""
        return {"count": 0, "bytes": 0}

    def make_queue(self):
        """Instruction queue the scheduler drains (DESIGN.md §11): the
        fused decode step wrapped as a degenerate 1-instruction queue."""
        return FusedQueue(self)

    def free_slots(self, slots: Sequence[int]) -> None:
        for s in slots:
            if not 0 <= s < self.num_slots:
                raise IndexError(f"slot {s} out of range")
        if self.paged:
            for s in slots:
                # no-op for never-admitted slots
                self.pool.free(self._owner(s))
                self.block_tables[s] = 0
                self._decodable.discard(s)
                self._worst.pop(s, None)

    # -- shared admission loop (template method) ---------------------------
    def prefill_into_slots(self, prompts, slots) -> np.ndarray:
        """Admit requests: one batch-1 prefill per prompt at its true
        length (row-wise identical to serving it solo; CP-padded and
        sequence-sharded when c > 1), scattered into the slot's batch row.
        Returns the first greedy token per request.

        In paged mode the prompt prefills straight into the slot's pages
        as one maximal chunk (one CP pass when c > 1) — the non-chunked
        protocol entry point over the chunked machinery (the scheduler's
        chunked path drives ``begin_prefill``/``prefill_chunk``/
        ``finish_prefill`` itself)."""
        first = np.zeros(len(slots), np.int32)
        for i, (prompt, slot) in enumerate(zip(prompts, slots)):
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            if self.paged:
                self.begin_prefill(slot, len(prompt))
                first[i] = self.prefill_whole(slot, prompt)
                self.finish_prefill(slot)
            else:
                logits, small = self._prefill_one(prompt)
                self._scatter(small, slot)
                first[i] = self._first_token(logits)[0]
        return first

    def _pad_prompt(self, prompt):
        """(CP-padded prompt, true-last-position index): pads with token 0
        to a multiple of c so the sequence axis shards equally.  The pad
        positions' KV rows are garbage the causal mask hides until decode
        overwrites them position by position (DESIGN.md §9)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        padded = np.pad(prompt, (0, (-len(prompt)) % self.c))
        # sliding-window configs serve prompts beyond max_len (the ring
        # cache keeps the last W positions) — same waiver as the
        # scheduler's admission check
        if not self.paged and len(padded) > self.max_len \
                and not self.cfg.sliding_window:
            raise ValueError(
                f"CP-padded prompt ({len(padded)}) exceeds max_len "
                f"{self.max_len}")
        return padded, len(prompt) - 1

    def _prefill_one(self, prompt):
        """(logits [1, v], seeded batch-1 cache) for one raw 1-D prompt."""
        raise NotImplementedError

    def _scatter(self, small, slot: int) -> None:
        """Write a batch-1 cache into the slot row (default: single slot
        cache pytree on ``self.cache`` via the donating ``self._write``)."""
        self.cache = self._write(self.cache, small, jnp.int32(slot))

    def _first_token(self, logits) -> np.ndarray:
        return np.asarray(jnp.argmax(logits, -1), np.int32)

    def _as_prompt(self, prompt) -> jnp.ndarray:
        return jnp.asarray(np.asarray(prompt, np.int32))[None, :]


class ModelBackend(_BackendBase):
    """GSPMD ``Model`` path (the runtime/engine.py lineage) behind the
    DecodeBackend protocol.  Single jit per decode step, donated slot cache,
    per-sequence positions through ``Model.decode_step``."""

    def __init__(self, cfg: ModelConfig, params, num_slots: int,
                 max_len: int = 256, paged: bool = False,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefix_cache: bool = False, pool: Optional[KVPool] = None,
                 owner_base: int = 0):
        super().__init__(cfg, num_slots, max_len, t=1, p=1, paged=paged,
                         page_size=page_size, num_pages=num_pages,
                         prefix_cache=prefix_cache, pool=pool,
                         owner_base=owner_base)
        self.model = get_model(cfg)
        self.params = params
        if self.paged:
            self.cache = self.model.init_paged_cache(self.pool.num_pages,
                                                     self.page_size)
            self._paged_fn = jax.jit(self.model.paged_step,
                                     donate_argnums=(1,))
        else:
            self.cache = self.model.init_cache(num_slots, max_len)
            self._prefill = jax.jit(
                functools.partial(self.model.prefill, max_len=max_len))
            self._step = jax.jit(self.model.decode_step, donate_argnums=(1,))
            self._write = jax.jit(_write_slot, donate_argnums=(0,))

    def _prefill_one(self, prompt):
        logits, small, _ = self._prefill(self.params, self._as_prompt(prompt))
        return logits, small

    def _paged_call(self, tokens, pos, bt, phase: str):
        logits, self.cache = self._paged_fn(
            self.params, self.cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(bt, jnp.int32))
        return logits

    def _copy_page(self, src: int, dst: int) -> None:
        self.cache = _copy_page_rows(self.cache, jnp.int32(src),
                                     jnp.int32(dst))

    def decode_step(self, tokens, pos) -> np.ndarray:
        if self.paged:
            return self._paged_decode(tokens, pos)
        logits, self.cache = self._step(
            self.params, self.cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(pos, jnp.int32))
        return self._first_token(logits)


class TPBackend(_BackendBase):
    """Explicit tensor-parallel engine (core/parallel_exec.py) behind the
    protocol: shard_map with hand-placed collectives — (2L+1) allreduce +
    1 logits all-gather per decode step, regardless of slot count.

    ``c > 1`` adds context parallelism on the same mesh (axes tp × cp;
    t=1 with c>1 is the pure-CP layout): prefill runs ``cp_prefill`` on
    the CP-padded prompt — per-layer ring KV exchange, one cp allreduce
    for the last hidden state — and the ring-assembled full cache lands in
    the slot row (contiguous) or the slot's pages (paged) exactly like a
    c=1 prefill's.  The decode step is the same jitted fn at any c, run
    replicated over the cp axis (DESIGN.md §9)."""

    def __init__(self, cfg: ModelConfig, params, num_slots: int,
                 max_len: int = 256, t: int = 2, unroll: bool = False,
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None, c: int = 1,
                 quant_collectives: Optional[str] = None,
                 quant_chunk: int = DEFAULT_QUANT_CHUNK,
                 prefix_cache: bool = False, pool: Optional[KVPool] = None,
                 owner_base: int = 0):
        super().__init__(cfg, num_slots, max_len, t=t, p=1, c=c,
                         paged=paged, page_size=page_size,
                         num_pages=num_pages,
                         quant_collectives=quant_collectives,
                         quant_chunk=quant_chunk,
                         prefix_cache=prefix_cache, pool=pool,
                         owner_base=owner_base)
        if cfg.family != "dense":
            raise ValueError("explicit TP engine covers the dense family")
        self._unroll = unroll
        self.mesh = px.make_tp_cp_mesh(t, c)
        self.params = px.tp_place_params(cfg, params, self.mesh)
        kv_spec = NamedSharding(
            self.mesh, P(None, None, None, "tp" if t > 1 else None, None))
        if self.paged:
            self._paged_fn = px.tp_paged_step(cfg, self.mesh, unroll=unroll)
            self.cache = {
                key: jnp.zeros((cfg.num_layers, self.pool.num_pages,
                                self.page_size, cfg.num_kv_heads,
                                cfg.head_dim), jnp.dtype(cfg.dtype),
                               device=kv_spec)
                for key in ("k", "v")}
            if c > 1:
                self._cp_fns = {}       # padded prompt len -> cp_prefill fn
                self._seed = jax.jit(_seed_pages, donate_argnums=(0,))
        else:
            self.cache_w = get_model(cfg).cache_width(max_len)
            if c > 1:
                self._prefill = px.cp_prefill(cfg, self.mesh,
                                              cache_w=self.cache_w,
                                              unroll=unroll)
            else:
                self._prefill = px.tp_prefill(cfg, self.mesh,
                                              cache_w=self.cache_w,
                                              unroll=unroll)
            self._step = px.tp_decode_step(
                cfg, self.mesh, unroll=unroll, vector_pos=True,
                quant_collectives=self.quant, quant_chunk=self.quant_chunk)
            self.cache = {
                key: jnp.zeros((cfg.num_layers, num_slots, self.cache_w,
                                cfg.num_kv_heads, cfg.head_dim),
                               jnp.dtype(cfg.dtype), device=kv_spec)
                for key in ("k", "v")}
            self._write = jax.jit(_write_slot, donate_argnums=(0,))

    def _cp_fn(self, cache_w: int):
        """CP prefill fn seeding a width-``cache_w`` staging cache (paged
        mode sizes it to the padded prompt so the page scatter writes
        exactly the allocated rows)."""
        if cache_w not in self._cp_fns:
            self._cp_fns[cache_w] = px.cp_prefill(
                self.cfg, self.mesh, cache_w=cache_w, unroll=self._unroll)
        return self._cp_fns[cache_w]

    def _prefill_one(self, prompt):
        if self.c > 1:
            padded, last = self._pad_prompt(prompt)
            fn = self._cp_fn(len(padded)) if self.paged else self._prefill
            return fn(self.params, self._as_prompt(padded), jnp.int32(last))
        return self._prefill(self.params, self._as_prompt(prompt))

    def _seed_slot_pages(self, small, slot: int) -> None:
        n = len(self.pool.block_table(self._owner(slot)))
        bt = jnp.asarray(self.block_tables[slot:slot + 1, :n])
        self.cache = self._seed(self.cache, small, bt)

    def _paged_call(self, tokens, pos, bt, phase: str):
        logits, self.cache = self._paged_fn(
            self.params, self.cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(bt, jnp.int32))
        return logits

    def _copy_page(self, src: int, dst: int) -> None:
        self.cache = _copy_page_rows(self.cache, jnp.int32(src),
                                     jnp.int32(dst))

    def decode_step(self, tokens, pos) -> np.ndarray:
        if self.paged:
            return self._paged_decode(tokens, pos)
        logits, self.cache = self._step(
            self.params, self.cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(pos, jnp.int32))
        return self._first_token(logits)

    def decode_step_hlo(self) -> str:
        """Compiled HLO of the slot decode step (collective-count checks)."""
        if self.paged:
            return self.paged_step_hlo(q_len=1, batch=self.num_slots)
        tok = jax.ShapeDtypeStruct((self.num_slots,), jnp.int32)
        pos = jax.ShapeDtypeStruct((self.num_slots,), jnp.int32)
        return self._step.lower(self.params, self.cache, tok,
                                pos).compile().as_text()

    def prefill_hlo(self, prompt_len: int) -> str:
        """Compiled HLO of one batch-1 prefill at a (CP-padded) prompt
        length — under c>1 the module shows the per-layer ring permutes
        and the cp allreduce next to the TP schedule, asserted against
        ``prefill_comm_ops`` / ``commodel.cp_comm_ops``."""
        if self.c > 1 and prompt_len % self.c:
            raise ValueError(f"prompt_len must be a multiple of c={self.c}")
        tok = jax.ShapeDtypeStruct((1, prompt_len), jnp.int32)
        if self.c > 1:
            fn = (self._cp_fn(prompt_len) if self.paged else self._prefill)
            last = jax.ShapeDtypeStruct((), jnp.int32)
            return fn.lower(self.params, tok, last).compile().as_text()
        return self._prefill.lower(self.params, tok).compile().as_text()

    def paged_step_hlo(self, q_len: int, batch: int = 1) -> str:
        """Compiled HLO of one paged pass at chunk length ``q_len`` — the
        per-chunk (and, at q_len=1, per-decode-step) collective-count
        check against ``commodel.chunked_prefill_ops``."""
        self._require_paged()
        tok = jax.ShapeDtypeStruct((batch, q_len), jnp.int32)
        pos = jax.ShapeDtypeStruct((batch,), jnp.int32)
        bt = jax.ShapeDtypeStruct((batch, self.pages_per_slot), jnp.int32)
        return self._paged_fn.lower(self.params, self.cache, tok, pos,
                                    bt).compile().as_text()


class PPBackend(_BackendBase):
    """PipelineEngine (pure PP when t=1, hybrid TP×CP×PP otherwise) behind
    the protocol: per-stage slot caches, one decode step = one token through
    all p stages with (p-1)·2 logged boundary transfers.

    ``c > 1`` CP-shards each stage's prefill over the stage's cp mesh axis
    (boundary hops shrink to [S/c, h/t] per worker); the ring-assembled
    per-stage caches land in the stage slot rows or page pools, and decode
    runs the unchanged per-stage steps replicated over cp (DESIGN.md §9).

    ``inflight > 1`` (DESIGN.md §11) splits the slots into ``inflight``
    *microbatch groups* of ``num_slots // inflight`` rows each.  The slot
    contiguous caches become per-group per-stage caches (``gcaches[g][s]``)
    so groups can occupy different stages concurrently; paged pools stay
    shared per stage (rounds are isolated by their disjoint block tables).
    The group decode round is driven instruction-by-instruction via
    ``start_round`` / ``run_stage`` / ``send_boundary`` by the
    ``DynamicPPQueue`` that ``make_queue`` returns."""

    def __init__(self, cfg: ModelConfig, params, num_slots: int,
                 max_len: int = 256, t: int = 1, p: int = 2,
                 unroll: bool = False, devices=None, paged: bool = False,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 c: int = 1, inflight: int = 1,
                 quant_collectives: Optional[str] = None,
                 quant_chunk: int = DEFAULT_QUANT_CHUNK,
                 prefix_cache: bool = False, pool: Optional[KVPool] = None,
                 owner_base: int = 0):
        super().__init__(cfg, num_slots, max_len, t=t, p=p, c=c,
                         paged=paged, page_size=page_size,
                         num_pages=num_pages,
                         quant_collectives=quant_collectives,
                         quant_chunk=quant_chunk,
                         prefix_cache=prefix_cache, pool=pool,
                         owner_base=owner_base)
        if cfg.family != "dense":
            raise ValueError("PipelineEngine covers the dense family")
        if inflight < 1 or num_slots % inflight:
            raise ValueError(
                f"inflight must divide num_slots: got inflight={inflight}, "
                f"num_slots={num_slots}")
        self.inflight = int(inflight)
        self.group_size = num_slots // self.inflight
        self.engine = px.PipelineEngine(cfg, t=t, p=p, c=c, unroll=unroll,
                                        devices=devices,
                                        quant_collectives=self.quant,
                                        quant_chunk=self.quant_chunk)
        self.staged = self.engine.prepare(params)

        def stage_cache(s, rows, width):
            """Stage s's [L_s, rows, width, kv, D] K/V leaves, created on
            the stage's own mesh (kv heads on "tp" when t > 1)."""
            lo, hi = px.stage_layer_range(cfg, p, s)
            spec = NamedSharding(
                self.engine.meshes[s],
                P(None, None, None, "tp" if t > 1 else None, None))
            return {key: jnp.zeros((hi - lo, rows, width, cfg.num_kv_heads,
                                    cfg.head_dim), jnp.dtype(cfg.dtype),
                                   device=spec)
                    for key in ("k", "v")}

        self.caches = []       # paged: per-stage page pools
        self.gcaches = None    # contiguous: per-group per-stage slot caches
        if self.paged:
            # per-stage page pools share ONE block-table space: logical page
            # j of a slot lives at physical page table[j] in every stage's
            # [L_s, P, ps, kv, D] pool
            self.caches = [stage_cache(s, self.pool.num_pages,
                                       self.page_size) for s in range(p)]
        else:
            self.cache_w = get_model(cfg).cache_width(max_len)

            self.gcaches = [[stage_cache(s, self.group_size, self.cache_w)
                             for s in range(p)]
                            for _ in range(self.inflight)]
        self._writes = [jax.jit(_write_slot, donate_argnums=(0,))
                        for _ in range(p)]
        if self.paged and c > 1:
            self._seed = jax.jit(_seed_pages, donate_argnums=(0,))
        self._drained = 0              # transfer-log cursor

    def _prefill_one(self, prompt):
        if self.c > 1:
            padded, last = self._pad_prompt(prompt)
            w = len(padded) if self.paged else self.cache_w
            return self.engine.prefill_with_cache(
                self.staged, self._as_prompt(padded), cache_w=w, last=last)
        return self.engine.prefill_with_cache(
            self.staged, self._as_prompt(prompt), cache_w=self.cache_w)

    def _scatter(self, small, slot: int) -> None:
        g, row = divmod(slot, self.group_size)
        self.gcaches[g] = [
            self._writes[s](self.gcaches[g][s], small[s], jnp.int32(row))
            for s in range(self.p)]

    def _seed_slot_pages(self, small, slot: int) -> None:
        n = len(self.pool.block_table(self._owner(slot)))
        bt = jnp.asarray(self.block_tables[slot:slot + 1, :n])
        self.caches = [self._seed(self.caches[s], small[s], bt)
                       for s in range(self.p)]

    def _paged_call(self, tokens, pos, bt, phase: str):
        logits, self.caches = self.engine.paged_pass(
            self.staged, self.caches, tokens, pos, bt, phase=phase)
        return logits

    def _copy_page(self, src: int, dst: int) -> None:
        s, d = jnp.int32(src), jnp.int32(dst)
        self.caches = [_copy_page_rows(c, s, d) for c in self.caches]

    def export_page(self, page: int) -> dict:
        """Full-depth page rows, stages concatenated over the layer axis —
        the same [L, ps, kv, D] unit the single-pool backends export."""
        self._require_paged()
        return {key: np.concatenate(
                    [np.asarray(c[key][:, page]) for c in self.caches])
                for key in ("k", "v")}

    def import_page(self, page: int, data: dict) -> int:
        self._require_paged()
        total = 0
        for s in range(self.p):
            lo, hi = px.stage_layer_range(self.cfg, self.p, s)
            rows = {key: jnp.asarray(np.asarray(data[key][lo:hi]),
                                     jnp.dtype(self.cfg.dtype))
                    for key in ("k", "v")}
            self.caches[s] = _write_page(self.caches[s], rows,
                                         jnp.int32(page))
            total += sum(int(a.nbytes) for a in rows.values())
        return total

    def decode_step(self, tokens, pos) -> np.ndarray:
        if self.paged:
            return self._paged_decode(tokens, pos)
        tokens = np.asarray(tokens, np.int32)
        pos = np.asarray(np.asarray(pos), np.int32)
        out = np.zeros(self.num_slots, np.int32)
        G = self.group_size
        for g in range(self.inflight):
            lo = g * G
            logits, self.gcaches[g] = self.engine.decode_once(
                self.staged, self.gcaches[g],
                jnp.asarray(tokens[lo:lo + G]), jnp.asarray(pos[lo:lo + G]))
            out[lo:lo + G] = self._first_token(logits)
        return out

    # -- instruction-queue surface (runtime/schedule.py, DESIGN.md §11) ----
    def make_queue(self):
        """Dynamic per-stage instruction queue at depth ``inflight``."""
        return DynamicPPQueue(self)

    def start_round(self, g: int, tokens, pos):
        """(stage-0 feed, per-group positions, block tables | None) for one
        decode round of group ``g``.  Paged mode extends the group's
        decode-eligible slots' pages HERE — before any instruction issues —
        so pool exhaustion (MemoryError) surfaces with the round not in
        flight and the preemption ladder can free pages safely."""
        G = self.group_size
        lo = g * G
        toks = np.asarray(tokens, np.int32)[lo:lo + G]
        pos_np = np.asarray(np.asarray(pos), np.int32)[lo:lo + G]
        if self.paged:
            full_pos = np.asarray(pos)
            with span("backend.pages") as sp:
                claimed = self.pool.pages_claimed
                for slot in sorted(self._decodable):
                    if lo <= slot < lo + G:
                        self._claim_guard(
                            lambda s=slot: self.pool.extend(
                                self._owner(s), int(full_pos[s]) + 1))
                        self._set_table(slot)
                self._apply_cow()
                bt = self.block_tables[lo:lo + G].copy()
                for i, slot in enumerate(range(lo, lo + G)):
                    if slot not in self._decodable:
                        bt[i] = 0            # scratch page (kvpool.py)
                sp.counts["pages"] = self.pool.pages_claimed - claimed
            x = self.engine.feed_tokens(toks[:, None], paged=True)
            return x, jnp.asarray(pos_np), jnp.asarray(bt, jnp.int32)
        return self.engine.feed_tokens(toks), jnp.asarray(pos_np), None

    def run_stage(self, g: int, s: int, x, pos, bt=None):
        """One queue-issued StageForward: stage ``s``'s jitted fn against
        group ``g``'s cache (contiguous) or the stage's shared page pool
        (paged; rounds stay isolated through their disjoint block tables).
        The donated cache is rebound here, so Python issue order serializes
        the data dependencies between overlapping rounds."""
        if self.paged:
            fn = self.engine.paged_stage_fns()[s]
            out, self.caches[s] = fn(self.staged[s], self.caches[s], x,
                                     pos, bt)
        else:
            fn = self.engine.decode_stage_fns(vector_pos=True)[s]
            out, self.gcaches[g][s] = fn(self.staged[s], self.gcaches[g][s],
                                         x, pos)
        return out

    def send_boundary(self, out, s: int):
        """Queue-issued BoundarySend/Recv pair: ship stage ``s``'s boundary
        to stage ``s+1``, logging its decode TransferRecords."""
        return self.engine.send_boundary(out, s, phase="decode")

    def drain_transfers(self) -> dict:
        recs = self.engine.transfers[self._drained:]
        self._drained = len(self.engine.transfers)
        return {"count": sum(r.count for r in recs),
                "bytes": sum(r.bytes for r in recs)}

    def stage_paged_hlo(self, stage: int, q_len: int = 1,
                        batch: int = 1) -> str:
        """Compiled HLO of one stage's paged pass at chunk length ``q_len``
        — asserted against ``commodel.hybrid_stage_collectives`` (counts are
        chunk-length-invariant, DESIGN.md §8)."""
        self._require_paged()
        tok = jnp.zeros((batch, q_len), jnp.int32)
        pos = jnp.zeros((batch,), jnp.int32)
        bt = jnp.zeros((batch, self.pages_per_slot), jnp.int32)
        return self.engine.stage_paged_hlo(self.staged, self.caches, tok,
                                           pos, bt, stage)

    def stage_decode_hlo(self, stage: int) -> str:
        """Compiled HLO of one stage's slot decode step (vector pos) at the
        microbatch-group batch — collective counts are batch-invariant, so
        the check is depth-independent."""
        fns = self.engine._decode_fns(vector_pos=True)
        caches = self.gcaches[0]
        pos = jnp.zeros((self.group_size,), jnp.int32)
        tok = jnp.zeros((self.group_size,), jnp.int32)
        x = jax.device_put(tok, NamedSharding(self.engine.meshes[0], P(None)))
        for i in range(stage):
            fn, _ = fns[i]
            out, _ = fn(self.staged[i],
                        jax.tree.map(jnp.copy, caches[i]), x, pos)
            x = self.engine._move_boundary(out, i, "hlo", log=False)
        fn, _ = fns[stage]
        return fn.lower(self.staged[stage], caches[stage], x,
                        pos).compile().as_text()


def make_backend(kind: str, cfg: ModelConfig, params, num_slots: int,
                 max_len: int = 256, t: int = 1, p: int = 1,
                 unroll: bool = False, paged: bool = False,
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 c: int = 1, inflight: int = 1,
                 quant_collectives: Optional[str] = None,
                 quant_chunk: int = DEFAULT_QUANT_CHUNK,
                 prefix_cache: bool = False,
                 pool: Optional[KVPool] = None,
                 owner_base: int = 0) -> DecodeBackend:
    """Backend factory keyed by engine kind: "gspmd" | "tp" | "pp".

    Degenerate layouts are rejected, not coerced — a silently bumped t/c/p
    would attribute measured SLOs to a layout the caller never asked for.
    ``paged=True`` swaps the contiguous slot cache for the KVPool-managed
    page pools and enables chunked prefill (DESIGN.md §8).  ``c > 1`` adds
    context-parallel prefill on the explicit engines (DESIGN.md §9): the
    pure-CP layout (t=1, c>1, p=1) goes through the "tp" kind — the
    single-stage explicit engine on a cp-only mesh.  ``inflight > 1``
    splits the slots into in-flight microbatch groups on the pp backend's
    dynamic instruction queue (DESIGN.md §11); the fused engines have no
    pipeline bubble to fill and reject it.  ``quant_collectives``
    ("int8" | "fp8", DESIGN.md §12) lowers the explicit engines' per-layer
    decode allreduces to the quantized two-step; GSPMD places its own
    collectives and the paged engines run full-width — both reject it.
    ``prefix_cache=True`` (DESIGN.md §13) attaches a cross-request
    ``PrefixIndex`` to the page pool: paged-only, c=1-only (the suffix
    prefill needs the chunk-offset path).  ``pool``/``owner_base``
    (DESIGN.md §14) make this backend share another backend's ``KVPool``
    under a disjoint slot-owner range — how the disaggregated prefill and
    decode pools address one page space while their device page pools stay
    separate (content crosses via ``export_page``/``import_page``).
    """
    kw = dict(paged=paged, page_size=page_size, num_pages=num_pages,
              prefix_cache=prefix_cache, pool=pool, owner_base=owner_base)
    if kind != "pp" and inflight != 1:
        raise ValueError(
            "in-flight microbatching fills the PP decode bubble; the "
            f"{kind!r} backend runs a fused step — inflight must be 1")
    qkw = dict(quant_collectives=quant_collectives, quant_chunk=quant_chunk)
    if kind == "gspmd":
        if c > 1:
            raise ValueError(
                "context parallelism needs the explicit engines — use the "
                "tp (single-stage) or pp backend with c > 1")
        if quant_collectives is not None:
            raise ValueError(
                "quantized collectives need the explicit engines' "
                "hand-placed psums — GSPMD places its own collectives; "
                "use the tp or pp backend")
        return ModelBackend(cfg, params, num_slots, max_len, **kw)
    if kind == "tp":
        if t < 2 and c < 2:
            raise ValueError(
                f"tp backend needs t >= 2 or c >= 2, got t={t} c={c}")
        return TPBackend(cfg, params, num_slots, max_len, t=t, c=c,
                         unroll=unroll, **kw, **qkw)
    if kind == "pp":
        if p < 2:
            raise ValueError(f"pp backend needs p >= 2, got p={p}")
        return PPBackend(cfg, params, num_slots, max_len, t=t, c=c, p=p,
                         unroll=unroll, inflight=inflight, **kw, **qkw)
    raise ValueError(f"unknown backend kind: {kind!r}")
