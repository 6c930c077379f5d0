"""Paged KV cache: fixed-size pages, per-request block tables, free-list
allocation — the serving-side analogue of IMAGine's memory-capacity scaling
argument.  Decode throughput scales with how many requests' KV state the
page pool can hold, not with the worst-case ``n_slots * max_len`` rectangle
the fixed-slot engine reserves.

Two pieces:

* :class:`KVPages` — the device-side page pool, a registered JAX pytree.
  Storage is head-major, ``(L, Hkv, P, page_size, Dh)`` per K and V:
  every layer and head sees the same physical page ids, so one
  ``(B, n_blocks)`` block table per request addresses all of them, and
  one (head, page) is a contiguous ``(page_size, Dh)`` tile — the block
  the fused kernel streams.  With ``kv_bits=8`` the pools are int8
  bit-planed (per-(token, head) scales ride along as ``(L, Hkv, P,
  page_size)`` bf16 pools) — the ``EnginePlan.kv_bits`` knob applied to
  the cache exactly as ``plan.bits`` is applied to the weights.

* :class:`PageAllocator` — the host-side free list and block-table
  bookkeeping: capacity-based admission (``can_admit``), on-demand page
  grants during decode (``ensure``), and whole-request reclaim
  (``free_slot``).  Physical page 0 is reserved as the *null page*: idle
  batch lanes and masked prefill positions scatter there, so the jitted
  model functions never need a dynamic shape or a write-predicate.

  As of the prefix-cache subsystem (``repro.serve.prefix_cache``) pages
  are **reference counted**: one physical page may back many lanes' block
  tables (a shared prompt prefix), and a page only becomes reclaimable
  when its refcount drops to 0.  Refcount-0 pages held by an attached
  prefix cache stay *resident* (cached, LRU-evictable) instead of
  returning to the free list; ``_take_page`` transparently evicts them
  when the free list runs dry.  All page release goes through
  ``free_slot`` / ``_release_page`` — nothing outside this module may
  touch the free list directly (CI greps for bypasses).

* :func:`fork_tail_page` — the device-side copy-on-write primitive: a
  cache hit that ends mid-page clones the donor's partially-filled tail
  page into a freshly-allocated private page, so the new request can keep
  writing without corrupting the shared bytes.

The allocator is deliberately numpy/host-side — the jitted paged decode
and chunked prefill steps (``repro.models.transformer``) only ever see the
``KVPages`` arrays plus ``(block_tables, pos, active)`` index arrays.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig
from repro.obs.telemetry import NULL_TELEMETRY

NULL_PAGE = 0  # physical page 0 is never allocated; garbage writes land here


class AuditError(AssertionError):
    """A runtime invariant audit failed (allocator or prefix cache).

    Subclasses ``AssertionError`` so test harnesses that assert on
    engine state treat an audit trip as a failed assertion, but keeps
    its own type so production callers can catch *audit* failures
    (state corruption — stop taking traffic) apart from ordinary
    assertion bugs.
    """

# families whose KV state is pageable (ssm/hybrid keep O(1) recurrent
# state and stay on the fixed-slot engine); the single source of truth
# for both init_kv_pages and ServeEngine's mode="auto" selection
PAGED_FAMILIES = ("dense", "vlm", "audio", "moe")


@dataclasses.dataclass(frozen=True)
class KVPages:
    """Device-side paged KV pool for all layers (a registered JAX pytree).

    ``k`` / ``v``: ``(L, Hkv, P, page_size, Dh)`` in the cache storage
    dtype (int8 when ``kv_bits=8``).  ``k_scale`` / ``v_scale``:
    per-(token, head) dequantization scales ``(L, Hkv, P, page_size)``,
    ``None`` unless the pool is quantized.  ``page_size`` and ``kv_bits``
    are static aux data.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: Optional[jnp.ndarray]
    v_scale: Optional[jnp.ndarray]
    page_size: int
    kv_bits: int

    # ------------------------------------------------------------- helpers
    @property
    def n_pages(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def replace(self, **kw) -> "KVPages":
        return dataclasses.replace(self, **kw)

    def nbytes(self) -> int:
        leaves = [self.k, self.v]
        if self.quantized:
            leaves += [self.k_scale, self.v_scale]
        return int(sum(l.size * l.dtype.itemsize for l in leaves))


def _kvpages_flatten(p: KVPages):
    children = ((jax.tree_util.DictKey("k"), p.k),
                (jax.tree_util.DictKey("v"), p.v),
                (jax.tree_util.DictKey("k_scale"), p.k_scale),
                (jax.tree_util.DictKey("v_scale"), p.v_scale))
    return children, (p.page_size, p.kv_bits)


def _kvpages_unflatten(aux, children) -> KVPages:
    page_size, kv_bits = aux
    k, v, ks, vs = children
    return KVPages(k, v, ks, vs, page_size, kv_bits)


jax.tree_util.register_pytree_with_keys(
    KVPages, _kvpages_flatten,
    lambda aux, children: _kvpages_unflatten(aux, children))


def init_kv_pages(cfg: ModelConfig, n_pages: int, page_size: int,
                  dtype=None, kv_bits: int = 0) -> KVPages:
    """Allocate an all-zeros page pool for ``cfg`` (attention families).

    ``kv_bits=8`` allocates int8 pools plus bf16 scale pools — the same
    layout :func:`repro.models.transformer.init_cache` uses for its int8
    cache, paged.
    """
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(
            f"paged KV serves attention-KV families only; {cfg.family!r} "
            "keeps O(1) state and stays on the fixed-slot engine")
    if kv_bits not in (0, 8):
        raise ValueError(f"kv_bits must be 0/8, got {kv_bits}")
    dtype = dtype or jnp.dtype(cfg.dtype)
    if kv_bits:
        dtype = jnp.int8
    dh, hkv, nl = cfg.resolved_head_dim, cfg.n_kv_heads, cfg.n_layers
    shape = (nl, hkv, n_pages, page_size, dh)
    k = jnp.zeros(shape, dtype)
    v = jnp.zeros(shape, dtype)
    ks = vs = None
    if kv_bits:
        sshape = (nl, hkv, n_pages, page_size)
        ks = jnp.zeros(sshape, jnp.bfloat16)
        vs = jnp.zeros(sshape, jnp.bfloat16)
    return KVPages(k, v, ks, vs, page_size, kv_bits)


def pages_for(n_tokens: int, page_size: int) -> int:
    """Physical pages needed to hold ``n_tokens``."""
    return max(0, math.ceil(n_tokens / page_size))


class PageAllocator:
    """Host-side block tables + refcounted free list over a
    :class:`KVPages` pool.

    ``n_slots`` batch lanes each own a ``(max_blocks,)`` block table row
    (logical block i -> physical page id; ``NULL_PAGE`` where unmapped) and
    a token count ``pos``.  Pages come from one shared free list, so total
    physical capacity is ``(n_pages - 1) * page_size`` tokens across all
    lanes instead of ``n_slots * max_len``.

    **Refcount invariants** (property-pinned by
    ``tests/test_prefix_cache.py``): a page mapped by ``k`` block tables
    has ``refcount == k``; refcounts never go negative; page 0 (the null
    page) is never allocated, freed, shared or evicted; a released page
    returns to the free list unless an attached prefix cache holds it
    resident (then it parks as an evictable cached page).
    """

    def __init__(self, n_pages: int, page_size: int, n_slots: int,
                 max_len: int, obs=None):
        self.obs = obs if obs is not None else NULL_TELEMETRY
        self.page_size = page_size
        self.n_pages = n_pages
        self.n_slots = n_slots
        self.max_blocks = pages_for(max_len, page_size)
        if n_pages < self.max_blocks + 1:
            raise ValueError(
                f"n_pages={n_pages} cannot hold one max_len={max_len} "
                f"request (needs {self.max_blocks} pages + the null page)")
        # page 0 is the null page; everything else starts free (LIFO reuse)
        self.free: List[int] = list(range(n_pages - 1, NULL_PAGE, -1))
        self.block_tables = np.full((n_slots, self.max_blocks), NULL_PAGE,
                                    np.int32)
        self.pos = np.zeros((n_slots,), np.int32)
        self._mapped: List[List[int]] = [[] for _ in range(n_slots)]
        self.refcount = np.zeros((n_pages,), np.int32)
        self._cache = None  # attached PrefixCache (eviction provider)
        self.chaos = None   # optional ft.ChaosInjector (page_grant site)

    # -------------------------------------------------------- prefix cache
    def attach_cache(self, cache) -> None:
        """Register a prefix cache as the resident-page owner + evictor.

        The cache keeps refcount-0 pages resident (``cache.holds``) and
        hands them back through ``cache.evict`` when the free list runs
        dry; the allocator's capacity arithmetic counts those pages as
        available.
        """
        self._cache = cache

    def _emit_pages(self) -> None:
        """Publish pool occupancy (free / cache-resident / evictable) to
        telemetry — the ``C`` counter series on the pages trace track."""
        self.obs.on_pages(
            len(self.free),
            self._cache.cached_pages if self._cache is not None else 0,
            self.evictable_pages)

    # ----------------------------------------------------------- capacity
    @property
    def free_pages(self) -> int:
        return len(self.free)

    @property
    def evictable_pages(self) -> int:
        """Cached refcount-0 pages the attached prefix cache could evict."""
        return self._cache.evictable_count() if self._cache is not None else 0

    @property
    def used_pages(self) -> int:
        return (self.n_pages - 1) - len(self.free)

    def can_allocate(self, n_pages: int) -> bool:
        """Could ``n_pages`` fresh pages be produced right now (free list
        plus evictable cached pages)?  The free list answers first — the
        evictable count is a tree walk and is only consulted when the
        free list alone is short (keeps the per-decode-token ``ensure``
        O(1) while pages remain free)."""
        if n_pages <= len(self.free):
            return True
        return n_pages <= len(self.free) + self.evictable_pages

    def can_admit(self, n_tokens: int) -> bool:
        """Capacity-based admission: is there room for a request whose
        prompt is ``n_tokens`` plus one decode token?"""
        return self.can_allocate(pages_for(n_tokens + 1, self.page_size))

    # --------------------------------------------------------- allocation
    def _take_page(self) -> Optional[int]:
        """Pop a free page, evicting cached refcount-0 pages if needed.

        The chaos hook fires *before* the pop: a fired ``page_grant``
        fault makes this grant fail exactly as a dry pool would, so
        every caller exercises its real out-of-capacity path (admission
        blocks, decode preempts, COW forks drop) on demand.
        """
        if self.chaos is not None and self.chaos.fire("page_grant"):
            return None
        if not self.free and self._cache is not None:
            self._cache.evict(1)
        if not self.free:
            return None
        return self.free.pop()

    def alloc_page(self, slot: int) -> Optional[int]:
        """Allocate one private page as ``slot``'s next block (refcount 1).
        Used for the copy-on-write fork target of a mid-page cache hit."""
        page = self._take_page()
        if page is None:
            return None
        self.refcount[page] = 1
        blk = len(self._mapped[slot])
        self._mapped[slot].append(page)
        self.block_tables[slot, blk] = page
        self._emit_pages()
        return page

    def map_shared(self, slot: int, pages: List[int]) -> None:
        """Map already-resident (cached) pages as ``slot``'s leading
        blocks, taking one reference on each — the prefix-cache hit path.
        Must run before any private allocation for the slot."""
        if self._mapped[slot]:
            raise ValueError(
                f"slot {slot} already holds pages; map the shared prefix "
                "before allocating private pages")
        for blk, page in enumerate(pages):
            if page == NULL_PAGE:
                raise ValueError("cannot share the null page")
            self.refcount[page] += 1
            if (self.refcount[page] == 1 and self._cache is not None
                    and self._cache.holds(page)):
                # 0 -> 1: the cached page leaves the evictable set.  The
                # shared prefix pins root-first, so each node's parent is
                # already pinned and the cache's upward walk is O(1).
                self._cache._on_pin(page)
            self._mapped[slot].append(page)
            self.block_tables[slot, blk] = page
        self._emit_pages()

    def ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow ``slot``'s block table to cover ``n_tokens`` logical tokens.
        Returns False (net allocating nothing) if the free list runs dry
        even after evicting cached pages.

        ``can_allocate`` pre-checks capacity, but a grant can still fail
        mid-loop (chaos at the ``page_grant`` site, or a racing evictable
        count); a partial grant is rolled back page-by-page so a False
        return always leaves the slot exactly as it was.
        """
        need = pages_for(n_tokens, self.page_size)
        if need > self.max_blocks:
            raise ValueError(
                f"slot {slot} wants {n_tokens} tokens > max_len capacity")
        have = len(self._mapped[slot])
        if need <= have:
            return True  # nothing to grant (the per-decode-token case)
        if not self.can_allocate(need - have):
            return False
        granted: List[int] = []
        for blk in range(have, need):
            page = self._take_page()
            if page is None:
                for g in reversed(granted):
                    blk_g = len(self._mapped[slot]) - 1
                    self._mapped[slot].pop()
                    self.block_tables[slot, blk_g] = NULL_PAGE
                    self._release_page(g)
                self._emit_pages()
                return False
            granted.append(page)
            self.refcount[page] = 1
            self._mapped[slot].append(page)
            self.block_tables[slot, blk] = page
        self._emit_pages()
        return True

    def _release_page(self, page: int) -> None:
        """Drop one reference; at refcount 0 the page returns to the free
        list unless the prefix cache holds it resident (then it stays as
        an evictable cached page).  The only legal way to free a page."""
        if page == NULL_PAGE:
            raise ValueError("the null page is never freed")
        self.refcount[page] -= 1
        if self.refcount[page] < 0:
            raise AssertionError(f"page {page} refcount went negative")
        if self.refcount[page] == 0:
            if self._cache is not None and self._cache.holds(page):
                # 1 -> 0: stays resident, re-enters the evictable set.
                # ``free_slot`` releases deepest-first, so each node's
                # parent is still pinned and the upward walk is O(1).
                self._cache._on_unpin(page)
            else:
                self.free.append(page)

    def _reclaim_evicted(self, page: int) -> None:
        """Return an evicted cache-resident page (refcount already 0) to
        the free list.  Called by the prefix cache only."""
        assert page != NULL_PAGE and self.refcount[page] == 0
        self.free.append(page)
        self._emit_pages()

    def free_slot(self, slot: int) -> None:
        """Release every page the slot maps (request retired or preempted).
        Shared pages survive under their other owners / the prefix cache."""
        for page in reversed(self._mapped[slot]):
            self._release_page(page)
        self._mapped[slot] = []
        self.block_tables[slot, :] = NULL_PAGE
        self.pos[slot] = 0
        self._emit_pages()

    def block_row(self, slot: int) -> np.ndarray:
        """The slot's block-table row (a copy — safe to hand to the tree)."""
        return self.block_tables[slot].copy()

    # -------------------------------------------------------------- audit
    def audit(self) -> None:
        """Prove the allocator's bookkeeping invariants; raise
        :class:`AuditError` naming the first violation.

        Checked (the refcount contract the prefix cache and schedulers
        build on):

        * the null page is never allocated, freed, or referenced;
        * the free list holds unique, in-range, refcount-0 pages,
          disjoint from every mapped page and every cache-resident page;
        * **refcount conservation** — ``refcount[p]`` equals the number
          of block-table references across all lanes (cache residency
          deliberately takes no refcount: a cached page is *defined* by
          refcount 0 + ``cache.holds``);
        * each block-table row is exactly its ``_mapped`` list followed
          by ``NULL_PAGE`` padding — the jitted steps only ever address
          live pages;
        * ``pos`` never exceeds the slot's mapped token capacity;
        * **page conservation** — every physical page is free, mapped,
          or cache-resident; nothing leaks.
        """
        def fail(msg: str) -> None:
            raise AuditError(f"PageAllocator.audit: {msg}")

        if self.refcount[NULL_PAGE] != 0:
            fail(f"null page has refcount {self.refcount[NULL_PAGE]}")

        # vectorized checks on the hot path; when one trips, the slow
        # per-element sweep below names the exact violation.  The audit
        # runs after every step under ServeConfig(audit=1), so its cost
        # is part of the serving budget (BENCH_chaos.json gates it).
        free_set = set(self.free)
        if len(free_set) != len(self.free):
            fail("free list holds duplicate pages")
        if self.free:
            f = np.asarray(self.free, dtype=np.int64)
            if f.min() <= NULL_PAGE or f.max() >= self.n_pages:
                p = int(f[(f <= NULL_PAGE) | (f >= self.n_pages)][0])
                fail(f"free list holds out-of-range page {p}")
            ref_f = self.refcount[f]
            if ref_f.any():
                p = int(f[np.nonzero(ref_f)[0][0]])
                fail(f"free page {p} has refcount {self.refcount[p]}")
        if self._cache is not None:
            both = free_set & set(self._cache.pages())
            if both:
                fail(f"page {min(both)} is both free and cache-resident")

        # refcount conservation: count block-table references per page
        flat: List[int] = []
        mapped_set = set()
        for slot in range(self.n_slots):
            mapped = self._mapped[slot]
            row = self.block_tables[slot]
            n = len(mapped)
            if n:
                ok = (min(mapped) > NULL_PAGE
                      and max(mapped) < self.n_pages
                      and row[:n].tolist() == mapped)
                if not ok:
                    for blk, page in enumerate(mapped):  # name it
                        if not (NULL_PAGE < page < self.n_pages):
                            fail(f"slot {slot} maps out-of-range "
                                 f"page {page}")
                        if row[blk] != page:
                            fail(f"slot {slot} block {blk}: table says "
                                 f"{row[blk]}, _mapped says {page}")
                flat.extend(mapped)
                mapped_set.update(mapped)
            if row[n:].any():  # NULL_PAGE == 0: padding must be all-zero
                fail(f"slot {slot} block table addresses pages past its "
                     f"{n} mapped blocks")
            cap = n * self.page_size
            if not (0 <= self.pos[slot] <= cap):
                fail(f"slot {slot} pos {self.pos[slot]} outside mapped "
                     f"capacity {cap}")
        expect = (np.bincount(np.asarray(flat, dtype=np.int64),
                              minlength=self.n_pages)
                  if flat else np.zeros((self.n_pages,), np.int64))
        bad = np.nonzero(expect != self.refcount)[0]
        if bad.size:
            p = int(bad[0])
            fail(f"page {p} refcount {self.refcount[p]} != "
                 f"{int(expect[p])} block-table references")
        if free_set & mapped_set:
            p = min(free_set & mapped_set)
            fail(f"page {p} is both free and mapped")

        # page conservation: free + mapped + cache-resident covers the pool
        accounted = free_set | mapped_set
        if self._cache is not None:
            accounted |= set(self._cache.pages())
        leaked = set(range(1, self.n_pages)) - accounted
        if leaked:
            fail(f"pages leaked (not free, mapped, or cached): "
                 f"{sorted(leaked)[:8]}")

    # -------------------------------------------------------------- views
    def device_tables(self, shardings=None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(block_tables, pos) as device arrays for the jitted steps.

        ``shardings``: optional ``(bt_sharding, pos_sharding)`` pair (from
        ``dist.sharding.batch_shardings`` — lane axis over the data axes).
        The numpy tables go straight to their mesh placement in one
        transfer — no default-device stop, no per-step reshard inside the
        jitted decode/prefill calls.
        """
        if shardings is not None:
            return (jax.device_put(self.block_tables, shardings[0]),
                    jax.device_put(self.pos, shardings[1]))
        return jnp.asarray(self.block_tables), jnp.asarray(self.pos)


# ---------------------------------------------------------------------------
# copy-on-write: fork a partially-filled tail page
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0,))
def fork_tail_page(pages: KVPages, src: jnp.ndarray,
                   dst: jnp.ndarray) -> KVPages:
    """Clone physical page ``src`` into ``dst`` across every layer (and the
    scale pools when quantized) — the copy-on-write step of a mid-page
    prefix-cache hit.

    The whole page is copied: the matched prefix slots are the bytes being
    shared, and every slot past the match point is overwritten by the
    request's own suffix prefill before it can ever be attended (positions
    ``>= pos`` are masked).  ``src``/``dst`` are traced scalars, so one
    compilation serves every fork; the pool is donated so XLA can update
    the buffers in place.
    """
    return pages.replace(**{
        name: a.at[:, :, dst].set(a[:, :, src])
        for name, a in page_arrays(pages).items()})


def page_arrays(pages: KVPages) -> dict:
    """The pool's arrays by field name (scale pools only when quantized);
    each carries the physical page axis at position 2."""
    out = {"k": pages.k, "v": pages.v}
    if pages.quantized:
        out["k_scale"], out["v_scale"] = pages.k_scale, pages.v_scale
    return out
