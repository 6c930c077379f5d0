"""Scheduling policy for the paged continuous-batching engine.

The scheduler owns the request queue and the slot (batch-lane) table and
makes three kinds of decisions, all host-side and all against the
:class:`~repro.serve.pages.PageAllocator`:

* **Admission** — FCFS, capacity-based: the head-of-queue request is
  admitted into a free lane only when the page pool can hold its whole
  prefill (prompt, plus any tokens it generated before a preemption) and
  one decode token.  Pages are granted up front, so chunked prefill never
  allocates mid-flight.

* **Chunked batched prefill** — every admitted-but-unfinished request
  contributes its next ≤ ``chunk`` prompt tokens to one batched
  ``prefill_chunk`` call (replacing the old per-token ``_prefill_slot``
  loop: one forward per chunk across all pending lanes instead of one
  decode step per prompt token per request).  Chunks interleave with
  decode steps, so long prompts do not stall running generations for
  their whole prefill.

* **Preemption** — when decode needs a page and the free list is dry, the
  *longest-running* request (earliest admission still resident) is
  evicted: its pages are reclaimed, and it re-enters the queue head with
  ``prompt + generated-so-far`` as its new prefill (recompute-style
  preemption — nothing is swapped out, greedy decode resumes exactly
  where it left off).  With a prefix cache attached, cache *eviction*
  always runs first (inside ``PageAllocator``): dropping an idle cached
  page is strictly cheaper than recomputing a live request.

With a :class:`~repro.serve.prefix_cache.PrefixCache` attached, admission
additionally matches the prompt against the radix tree: matched full
pages are mapped shared (refcounted) into the lane's block table, a
mid-page match records a pending copy-on-write fork (the engine runs the
device copy before the next prefill step), and the lane's prefill offset
starts at the matched length — the batched ``prefill_chunk`` call then
computes **only the unmatched suffix** (its per-request ``pos0`` offsets
have carried arbitrary starts since PR 2).

:class:`BudgetScheduler` layers SLA-aware policy on top: a per-step
**token budget** shared between decode (one token per ready lane, always
funded first) and chunked prefill (sliced into whatever budget remains,
so a 30k-token prompt spreads across steps without ever stalling active
decode lanes), **priority classes** (``interactive``/``default``/
``batch``) with weighted fair-share virtual-time accounting per
``(tenant, priority)`` key, and admission that skips over blocked
higher-vt requests instead of head-of-line blocking.  Both policies
drive the *same* lane-independent chunked-prefill kernel, so greedy
token output is identical under either — scheduling changes latency,
never content.
"""

from __future__ import annotations

import collections
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.obs.telemetry import NULL_TELEMETRY
from repro.serve.pages import PageAllocator, pages_for

PrefillBatch = Tuple[np.ndarray, np.ndarray, np.ndarray,
                     List[Tuple[int, int]]]

# weighted fair-share classes: an active interactive key receives 8x the
# prefill+decode tokens of an active batch key (never starving either —
# virtual time advances for whoever is served, so every key's turn comes)
PRIORITY_WEIGHTS: Dict[str, float] = {
    "interactive": 8.0,
    "default": 4.0,
    "batch": 1.0,
}


class PagedScheduler:
    """Admission + prefill batching + preemption over ``n_slots`` lanes."""

    def __init__(self, alloc: PageAllocator, chunk: int,
                 prefix_cache=None, obs=None):
        self.alloc = alloc
        self.obs = obs if obs is not None else NULL_TELEMETRY
        self.chunk = int(chunk)
        if self.chunk < 1:
            raise ValueError(f"prefill chunk must be >= 1, got {chunk}")
        self.n_slots = alloc.n_slots
        self.queue: Deque = collections.deque()
        self.slot_req: List[Optional[object]] = [None] * self.n_slots
        self.preemptions = 0
        self._admit_seq = 0
        self.prefix_cache = prefix_cache
        # (slot, src_page, dst_page) device copies the engine must run
        # before the next prefill/decode step touches the forked pages.
        # Tagged with the owning slot so cancellation/preemption can drop
        # a freed slot's forks before the dst page is reused (a fork into
        # a page that went back to the free list would corrupt whoever
        # reallocates it).
        self.pending_forks: List[Tuple[int, int, int]] = []
        # prefill tokens actually computed (the bench's ∝-unique-suffix
        # gate reads this; cache hits keep it below total prompt tokens)
        self.prefill_computed = 0

    # ------------------------------------------------------------- queue
    def submit(self, req) -> None:
        self.queue.append(req)

    def has_work(self) -> bool:
        return bool(self.queue) or any(
            r is not None for r in self.slot_req)

    def drop_forks(self, slot: int) -> None:
        """Discard pending copy-on-write forks owned by ``slot`` (the
        request was cancelled or preempted before the engine ran the
        device copy; its dst page is about to return to the free list)."""
        self.pending_forks = [
            f for f in self.pending_forks if f[0] != slot]

    # --------------------------------------------------------- admission
    def admit(self) -> None:
        """FCFS admission while a lane is free and capacity allows.

        The head-of-queue request blocks the queue when it does not fit
        (arrival order is preserved exactly).
        """
        for slot in range(self.n_slots):
            if not self.queue:
                return
            if self.slot_req[slot] is not None:
                continue
            if not self._try_admit(slot, self.queue[0]):
                return  # head-of-line blocks: keep arrival order
            self.queue.popleft()

    def _try_admit(self, slot: int, req) -> bool:
        """Admit ``req`` into free lane ``slot`` if capacity allows;
        returns False (leaving the allocator untouched) otherwise.  The
        caller owns the queue — on success it must remove ``req`` itself.

        With a prefix cache: the prompt is matched against the radix tree
        *before* the capacity check — shared full pages cost nothing, so
        a request whose prefix is resident can be admitted into a pool
        that could not hold its cold prefill.  Pages for the whole
        (suffix) prefill plus one decode token are still granted up
        front, so chunked prefill never allocates mid-flight.
        """
        toks = req.prefill_tokens
        total = pages_for(len(toks) + 1, self.alloc.page_size)
        # hopeless-case prefilter: even a best-case match (every full
        # page shared) cannot fit — skip the tree walk + pin/rollback
        # churn this blocked request would otherwise pay on every
        # scheduler iteration until capacity frees
        if not self.alloc.can_allocate(
                total - len(toks) // self.alloc.page_size):
            return False
        match = None
        if self.prefix_cache is not None:
            match = self.prefix_cache.match(toks)
        n_shared = len(match.full_pages) if match else 0
        if n_shared:
            # pin the matched pages (refcount++) *before* the capacity
            # check: a refcount-0 cached page counts as evictable
            # capacity, and a page about to be shared must not be
            # promised to the eviction path as well
            self.alloc.map_shared(slot, match.full_pages)
        need = total - n_shared
        if not self.alloc.can_allocate(need):
            if n_shared:
                self.alloc.free_slot(slot)  # unpin; pages stay cached
            return False
        # the COW fork target is granted first: a failed grant (pool dry
        # after all, or chaos at the page_grant site) downgrades the
        # mid-page match — the partial tokens simply prefill normally —
        # rather than failing the whole admission
        fork = None
        if match is not None and match.partial is not None:
            dst = self.alloc.alloc_page(slot)
            if dst is None:
                match.partial = None
                match.matched_tokens = n_shared * self.alloc.page_size
            else:
                fork = (slot, match.partial[0], dst)
        matched = match.matched_tokens if match is not None else 0
        self.alloc.pos[slot] = matched
        if not self.alloc.ensure(slot, len(toks) + 1):
            # capacity said yes but the grant still failed mid-loop
            # (chaos-injected, or an evictable page vanished): roll the
            # whole admission back — shared pins and the fork target all
            # release through free_slot, the request stays queued
            self.alloc.free_slot(slot)
            return False
        self.slot_req[slot] = req
        req.admit_seq = self._admit_seq
        self._admit_seq += 1
        if match is not None:
            self.prefix_cache.hits += bool(matched)
            self.prefix_cache.misses += not matched
            self.prefix_cache.hit_tokens += matched
            if matched:
                self.obs.on_cache_hit(req.rid, matched,
                                      match.partial is not None)
            else:
                self.obs.on_cache_miss(req.rid)
            if fork is not None:
                self.pending_forks.append(fork)
                self.prefix_cache.cow_forks += 1
        req.prefill_pos = matched
        req.cached_tokens = matched
        self.obs.on_admit(req.rid, slot, matched)
        return True

    # ----------------------------------------------------------- prefill
    def _pick_prefill(self) -> List[Tuple[int, int]]:
        """``(slot, n_tokens)`` prefill work for this step: every pending
        lane advances by up to ``chunk`` tokens (the FCFS policy has no
        budget — subclasses ration here)."""
        picks = []
        for slot, req in enumerate(self.slot_req):
            if req is None or req.prefill_pos >= len(req.prefill_tokens):
                continue
            picks.append(
                (slot, min(self.chunk,
                           len(req.prefill_tokens) - req.prefill_pos)))
        return picks

    def charge_decode(self, ready: List[Tuple[int, object]]) -> None:
        """Account for one decode token per ready lane this step (called
        by the engine right before the decode dispatch).  FCFS keeps no
        accounts; the budget scheduler charges fair-share virtual time."""

    def prefill_batch(self, audio_codebooks: int = 0
                      ) -> Optional[PrefillBatch]:
        """Assemble the next chunked prefill batch across pending lanes.

        Lane selection and per-lane token counts come from
        ``_pick_prefill`` (policy); this method only assembles the padded
        arrays.  Returns ``(tokens, pos0, seq_lens, [(slot, n_real),
        ...])`` with ``tokens`` shaped ``(n_slots, chunk)`` (``(n_slots,
        chunk, K)`` for audio), or ``None`` when nothing is pending.
        """
        lanes: List[Tuple[int, int]] = []
        c = self.chunk
        tokens = np.zeros((self.n_slots, c), np.int32)
        pos0 = np.zeros((self.n_slots,), np.int32)
        seq_lens = np.zeros((self.n_slots,), np.int32)
        for slot, n_real in self._pick_prefill():
            req = self.slot_req[slot]
            tokens[slot, :n_real] = req.prefill_tokens[
                req.prefill_pos:req.prefill_pos + n_real]
            pos0[slot] = req.prefill_pos
            seq_lens[slot] = req.prefill_pos + n_real
            lanes.append((slot, n_real))
            self.prefill_computed += n_real
        if not lanes:
            return None
        if audio_codebooks > 1:  # one EnCodec token broadcast per codebook
            tokens = np.broadcast_to(
                tokens[..., None],
                tokens.shape + (audio_codebooks,)).copy()
        return tokens, pos0, seq_lens, lanes

    def decode_lanes(self) -> List[Tuple[int, object]]:
        """Lanes whose request is fully prefilled and ready to decode."""
        return [
            (s, r) for s, r in enumerate(self.slot_req)
            if r is not None
            and r.prefill_pos >= len(r.prefill_tokens)
            and r.pending is not None
        ]

    def lane_mask(self, slots) -> np.ndarray:
        """(n_slots,) bool lane-activity mask for the jitted decode step.

        The mask's batch axis is the one the mesh shards over ``data`` —
        building it here keeps every lane-indexed array the scheduler
        hands the device in one place.
        """
        mask = np.zeros((self.n_slots,), bool)
        for s in slots:
            mask[s] = True
        return mask

    # -------------------------------------------------------- preemption
    def grant_decode_page(self, slot: int) -> bool:
        """Make room for slot's next decode token, preempting the
        longest-running other request if the free list is dry.  Returns
        False only when no victim remains (the lane must then wait)."""
        if self.slot_req[slot] is None:
            return False  # no resident request: never grow an empty slot
        want = int(self.alloc.pos[slot]) + 1
        while not self.alloc.ensure(slot, want):
            victim_slot = self._pick_victim(exclude=slot)
            if victim_slot is None:
                return False
            self._preempt(victim_slot)
        return True

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """Longest-running resident request = earliest admission."""
        best, best_seq = None, None
        for slot, req in enumerate(self.slot_req):
            if req is None or slot == exclude:
                continue
            seq = req.admit_seq
            if best_seq is None or seq < best_seq:
                best, best_seq = slot, seq
        return best

    def _preempt(self, slot: int) -> None:
        req = self.slot_req[slot]
        self.obs.on_preempt(req.rid, slot)
        self.alloc.free_slot(slot)
        self.slot_req[slot] = None
        # recompute-style: everything generated so far becomes prefill
        # (a resident prefix in the cache will be re-matched at re-admission)
        req.prefill_tokens = list(req.prompt) + list(req.output)
        req.prefill_pos = 0
        req.cached_tokens = 0
        req.last_logits = None
        req.pending = None
        req.preemptions += 1
        self.preemptions += 1
        self.drop_forks(slot)
        self.queue.appendleft(req)

    def preempt_storm(self) -> int:
        """Preempt **every** resident request (the chaos injector's
        ``preempt_storm`` site — a mass-eviction drill).  Recompute-style
        preemption is token-preserving, so a storm costs latency and
        prefill compute but can never change greedy output; the drill
        asserts exactly that.  Returns the number of lanes preempted.
        """
        n = 0
        for slot in range(self.n_slots):
            if self.slot_req[slot] is not None:
                self._preempt(slot)
                n += 1
        return n


class BudgetScheduler(PagedScheduler):
    """SLA-aware scheduling: per-step token budget + weighted fair share.

    Policy deltas over the FCFS base (the data path — chunked prefill,
    page grants, preemption — is inherited unchanged, so greedy output
    is token-identical under either scheduler):

    * **Per-step token budget** (``step_tokens``): decode is funded
      first — every ready lane advances one token every step, so a long
      prompt's prefill can never stall active generations.  Whatever
      budget remains is rationed to chunked prefill in fair-share order;
      a 30k-token prompt is sliced across as many steps as the budget
      dictates.  Completing a prompt's prefill reserves one extra token
      (its first decode happens the same engine step); if that reserve
      does not fit, the tail is deferred one step so the budget holds as
      a hard per-step invariant.

    * **Weighted fair share** across ``(tenant, priority)`` keys —
      classic virtual-time WFQ: serving ``n`` tokens to a key advances
      its virtual time by ``n / weight`` (weights from
      :data:`PRIORITY_WEIGHTS`), and both admission order and prefill
      rationing serve lowest-virtual-time first.  An idle key's clock is
      floor-bumped to the busiest-behind key on reactivation, so sleeping
      does not bank credit, and an active ``batch`` key keeps receiving
      ``1/(1+Σweights)`` of the tokens no matter how much ``interactive``
      traffic arrives — priority speeds the favored class up, it never
      starves the rest.

    * **Out-of-order admission**: a blocked candidate (pool too full) no
      longer head-of-line blocks — later queued requests that fit are
      admitted (lowest virtual time first).  Arrival order still breaks
      ties within a key via rid.

    Load shedding (bounded admission queue) lives in
    :meth:`ServeEngine.submit` / the front-end, not here — the scheduler
    never refuses work it has already been handed.
    """

    def __init__(self, alloc: PageAllocator, chunk: int,
                 prefix_cache=None, obs=None, *, step_tokens: int,
                 weights: Optional[Dict[str, float]] = None):
        super().__init__(alloc, chunk, prefix_cache=prefix_cache, obs=obs)
        self.step_tokens = int(step_tokens)
        # >= 2: one token of prefill progress plus the completion reserve
        # must fit in an otherwise-idle step, or a 1-token-tail prompt
        # could be deferred forever
        if self.step_tokens < 2:
            raise ValueError(
                f"step_tokens must be >= 2, got {step_tokens}")
        self.weights = dict(weights or PRIORITY_WEIGHTS)
        self._vtime: Dict[Tuple[str, str], float] = {}

    # ------------------------------------------------------- fair share
    def _key(self, req) -> Tuple[str, str]:
        return (getattr(req, "tenant", "default"),
                getattr(req, "priority", "default"))

    def _weight(self, req) -> float:
        return self.weights.get(getattr(req, "priority", "default"), 1.0)

    def _vfloor(self) -> float:
        """Lowest virtual time among currently active keys (queued or
        resident) — the reactivation floor for idle keys."""
        keys = {self._key(r) for r in self.queue}
        keys.update(self._key(r) for r in self.slot_req if r is not None)
        vals = [self._vtime[k] for k in keys if k in self._vtime]
        return min(vals, default=0.0)

    def _charge(self, req, n_tokens: int) -> None:
        """Advance ``req``'s key by ``n_tokens`` of service."""
        k = self._key(req)
        vt = max(self._vtime.get(k, 0.0), self._vfloor())
        self._vtime[k] = vt + n_tokens / self._weight(req)

    def _service_order(self, reqs):
        """Lowest virtual time first; fresh keys start at the floor and
        break ties by weight (heavier class first), then arrival."""
        floor = self._vfloor()
        return sorted(
            reqs, key=lambda r: (self._vtime.get(self._key(r), floor),
                                 -self._weight(r), r.rid))

    # --------------------------------------------------------- admission
    def admit(self) -> None:
        """Admit queued requests in fair-share order, skipping over any
        that don't fit (no head-of-line blocking)."""
        free = [s for s in range(self.n_slots)
                if self.slot_req[s] is None]
        if not free or not self.queue:
            return
        for req in self._service_order(list(self.queue)):
            if not free:
                return
            if self._try_admit(free[0], req):
                self.queue.remove(req)
                free.pop(0)

    # ----------------------------------------------------------- prefill
    def _ready_decoders(self) -> int:
        """Lanes that will consume a decode token this step."""
        return sum(1 for _, r in self.decode_lanes()
                   if len(r.output) < r.max_new_tokens)

    def _pick_prefill(self) -> List[Tuple[int, int]]:
        """Ration the step's remaining token budget to pending prefills,
        lowest virtual time first."""
        budget = self.step_tokens - self._ready_decoders()
        slot_of = {id(r): s for s, r in enumerate(self.slot_req)
                   if r is not None}
        pending = [r for r in self.slot_req
                   if r is not None
                   and r.prefill_pos < len(r.prefill_tokens)]
        picks: List[Tuple[int, int]] = []
        for req in self._service_order(pending):
            if budget <= 0:
                break
            slot = slot_of[id(req)]
            rem = len(req.prefill_tokens) - req.prefill_pos
            n = min(self.chunk, rem, budget)
            if n == rem and n + 1 > budget:
                # completing the prefill costs its first decode token in
                # the same step; defer the tail rather than overshoot
                n -= 1
            if n <= 0:
                continue
            picks.append((slot, n))
            budget -= n + (1 if n == rem else 0)
            self._charge(req, n)
        return picks

    def charge_decode(self, ready: List[Tuple[int, object]]) -> None:
        for _, req in ready:
            self._charge(req, 1)
