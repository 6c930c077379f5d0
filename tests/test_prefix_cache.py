"""Prefix-cache subsystem: radix-tree KV reuse with ref-counted
copy-on-write pages (``repro.serve.prefix_cache``).

The pinning claim: **greedy decode with prefix-cache hits is
token-identical to cold-path decode** — across full-page and mid-page
(COW-fork) split points, ``kv_bits`` 0 and 8, unsharded and an
8-host-device ``(data, model)`` mesh, after eviction, and under
preemption.  A hit only substitutes resident KV bytes for recomputed
ones; it must never change a token.

Plus the allocator-invariant property tests (``test_sharding_props``
style): refcounts never negative, the null page is never allocated /
freed / shared / evicted, alloc-free-alloc reuses pages, and eviction
only ever touches refcount-0 cached pages.
"""

import os
import subprocess
import sys
import textwrap

from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import EngineConfig, ServeConfig
from repro.models import init_params
from repro.serve import (
    PageAllocator,
    PrefixCache,
    ServeEngine,
    fork_tail_page,
    init_kv_pages,
)
from repro.serve.pages import NULL_PAGE

from conftest import reduced_f32

PS = 4  # page size for every engine test in this file

# prompt geometry (page_size=4): A's pages cover [1..4][5..8][9..12];
# B diverges mid-page inside A's third page (tokens 9, 10 then 60, 61
# — kept inside every arch's reduced vocab: musicgen's is only 64),
# C repeats A exactly (the cap leaves 1 suffix token -> partial match of
# the last page), D shares nothing.
A = list(range(1, 13))
B = list(range(1, 11)) + [60, 61]
C = list(A)
D = [71, 72, 73, 74, 75, 76, 77, 78, 79]


def _gen(cfg, params, prompts, *, prefix_cache, n_slots=1, max_len=32,
         max_new=5, n_pages=None, kv_bits=0, prefill_chunk=3):
    scfg = ServeConfig(
        max_new_tokens=max_new,
        engine=EngineConfig(kv_bits=kv_bits, backend="reference"))
    eng = ServeEngine(cfg, params, scfg, n_slots=n_slots, max_len=max_len,
                      mode="paged", page_size=PS, n_pages=n_pages,
                      prefill_chunk=prefill_chunk,
                      prefix_cache=prefix_cache)
    for p in prompts:
        eng.submit(list(p))
    return eng, sorted(eng.run(), key=lambda r: r.rid)


def _assert_identical(cold, hot, tag):
    for a, b in zip(cold, hot):
        assert a.output == b.output, (tag, a.rid, a.output, b.output)


# ---------------------------------------------------------------- identity
@pytest.mark.parametrize("kv_bits", [0, 8])
def test_hits_token_identical_full_and_mid_page(rng, kv_bits):
    """Full-page and mid-page (COW) split points, kv_bits 0/8: cache-hit
    greedy decode matches cold decode token for token, and the hit path
    really ran (hits, forks, and fewer prefill tokens computed)."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    prompts = [A, B, C, D]
    e0, cold = _gen(cfg, params, prompts, prefix_cache=False,
                    kv_bits=kv_bits)
    e1, hot = _gen(cfg, params, prompts, prefix_cache=True,
                   kv_bits=kv_bits)
    _assert_identical(cold, hot, f"kv{kv_bits}")
    st_ = e1.prefix_stats()
    assert st_["hits"] >= 2 and st_["cow_forks"] >= 2, st_
    # B's match ends mid-page (10 tokens: 2 full pages + a 2-token fork);
    # C's match is capped at len-1 = 11 (2 full pages + a 3-token fork)
    assert st_["hit_tokens"] == 10 + 11, st_
    # prefill compute scales with the unique suffix, not the total prompt
    assert e1.prefill_computed == e0.prefill_computed - st_["hit_tokens"]


def test_full_page_split_no_fork(rng):
    """A shared prefix that ends exactly on a page boundary is served from
    full shared pages alone — refcounted, no COW copy."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    base = list(range(1, 9))                     # 8 tokens = 2 full pages
    prompts = [base + [30 + i, 40 + i] for i in range(3)]
    e0, cold = _gen(cfg, params, prompts, prefix_cache=False)
    e1, hot = _gen(cfg, params, prompts, prefix_cache=True)
    _assert_identical(cold, hot, "full-page")
    st_ = e1.prefix_stats()
    assert st_["cow_forks"] == 0, st_
    assert st_["hit_tokens"] == 2 * 8, st_      # two later requests hit


def test_concurrent_lanes_and_chunk_sizes(rng):
    """Hits with several lanes in flight and across chunk geometries keep
    identity (per-request prefill offsets ride the batched chunk path)."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    prompts = [A, B, C, D, B, A]
    _, ref = _gen(cfg, params, prompts, prefix_cache=False)
    for n_slots in (2, 3):
        for chunk in (1, 2, 5):
            _, hot = _gen(cfg, params, prompts, prefix_cache=True,
                          n_slots=n_slots, prefill_chunk=chunk)
            _assert_identical(ref, hot, (n_slots, chunk))


def test_identity_after_eviction(rng):
    """A pool too small to keep every prefix resident forces LRU eviction
    of refcount-0 cached pages; evicted prefixes recompute cold and the
    stream stays token-identical."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    prompts = [A, B, C, D, B, A]
    e0, cold = _gen(cfg, params, prompts, prefix_cache=False, n_pages=9,
                    max_new=8)
    e1, hot = _gen(cfg, params, prompts, prefix_cache=True, n_pages=9,
                   max_new=8)
    _assert_identical(cold, hot, "eviction")
    assert e1.prefix_cache.evicted_pages > 0
    # drained engine: every surviving page is either free or cached, and
    # no references remain
    assert e1.alloc.used_pages == e1.prefix_cache.cached_pages
    assert e1.alloc.refcount.sum() == 0
    assert (e1.alloc.refcount >= 0).all()


def test_identity_under_preemption(rng):
    """Preemption (recompute-style) composes with the cache: the preempted
    request re-matches whatever prefix is still resident on re-admission
    and the greedy stream is unchanged."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    prompts = [A, B, C, D]
    e0, cold = _gen(cfg, params, prompts, prefix_cache=False, n_slots=3,
                    max_len=48, n_pages=14, max_new=16)
    e1, hot = _gen(cfg, params, prompts, prefix_cache=True, n_slots=3,
                   max_len=48, n_pages=14, max_new=16)
    assert e1.preemptions > 0
    _assert_identical(cold, hot, "preemption")


@pytest.mark.parametrize("arch", ["gemma3-27b", "qwen3-moe-235b-a22b",
                                  "musicgen-medium"])
def test_hits_token_identical_other_families(arch, rng):
    """Sliding-window / moe / audio families through the same tree."""
    cfg = reduced_f32(arch, capacity_factor=8.0)
    params = init_params(cfg, rng)
    prompts = [A, B, C]
    _, cold = _gen(cfg, params, prompts, prefix_cache=False)
    e1, hot = _gen(cfg, params, prompts, prefix_cache=True)
    _assert_identical(cold, hot, arch)
    assert e1.prefix_stats()["hits"] >= 2


def test_prefix_cache_on_mesh_token_identical():
    """8 forced host devices, (data=4, model=2) mesh: prefix-cache hits on
    the sharded pool (pages over data, heads over model; tree/refcounts
    host-side like block tables) match the unsharded cold stream."""
    pre = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, "src"); sys.path.insert(0, "tests")
        import jax
        from conftest import reduced_f32
        from repro.config.base import EngineConfig, ServeConfig
        from repro.dist import make_mesh
        from repro.models import init_params
        from repro.serve import ServeEngine

        cfg = reduced_f32("qwen2.5-3b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        A = list(range(1, 13)); B = list(range(1, 11)) + [99, 100]
        prompts = [A, B, list(A), list(B)]

        def gen(mesh=None, prefix_cache=False, kv_bits=0):
            scfg = ServeConfig(max_new_tokens=6, engine=EngineConfig(
                kv_bits=kv_bits, backend="reference"))
            eng = ServeEngine(cfg, params, scfg, n_slots=2, max_len=32,
                              mode="paged", page_size=4, prefill_chunk=3,
                              prefix_cache=prefix_cache, mesh=mesh)
            for p in prompts:
                eng.submit(list(p))
            return eng, sorted(eng.run(), key=lambda r: r.rid)

        mesh = make_mesh((4, 2), ("data", "model"))
        for kv in (0, 8):
            _, cold = gen(kv_bits=kv)
            e, hot = gen(mesh=mesh, prefix_cache=True, kv_bits=kv)
            kspec = e.pages.k.sharding.spec
            assert "data" in str(kspec) and "model" in str(kspec), kspec
            st = e.prefix_stats()
            assert st["hits"] >= 2 and st["cow_forks"] >= 1, st
            for a, b in zip(cold, hot):
                assert a.output == b.output, (kv, a.rid, a.output, b.output)
            print("kv", kv, "mesh hit == unsharded cold:", st)
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", pre], capture_output=True,
                         text=True, cwd=repo, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]


# --------------------------------------------------------- tree mechanics
def test_match_insert_semantics():
    alloc = PageAllocator(n_pages=17, page_size=4, n_slots=2, max_len=32)
    cache = PrefixCache(alloc)
    alloc.attach_cache(cache)
    assert alloc.ensure(0, 12)                    # 3 private pages
    row = alloc.block_row(0)
    toks = list(range(100, 112))                  # 12 tokens = 3 full pages
    assert cache.insert(toks, row) == 3
    assert cache.cached_pages == 3

    # full-page + mid-page match, capped at len-1
    m = cache.match(toks)                         # identical prompt
    assert [int(p) for p in m.full_pages] == [int(row[0]), int(row[1])]
    assert m.partial == (int(row[2]), 3)          # 3 of 4 tail tokens
    assert m.matched_tokens == 11                 # never the full prompt

    m2 = cache.match(toks[:10] + [7, 7])          # diverges mid-page 3
    assert m2.partial == (int(row[2]), 2) and m2.matched_tokens == 10

    m3 = cache.match([1] + toks)                  # different first token
    assert not m3 and m3.matched_tokens == 0

    m4 = cache.match(toks[:4])                    # 4 tokens: cap -> 3 (COW)
    assert m4.full_pages == [] and m4.partial == (int(row[0]), 3)

    # duplicate insert is a no-op; a foreign row with the same tokens
    # keeps the first owner's pages
    assert cache.insert(toks, row) == 0
    assert alloc.ensure(1, 12)
    assert cache.insert(toks, alloc.block_row(1)) == 0
    assert cache.cached_pages == 3


def test_fork_tail_page_copies_all_layers_and_scales():
    cfg = reduced_f32("qwen2.5-3b")
    for kv_bits in (0, 8):
        pages = init_kv_pages(cfg, 5, 4, kv_bits=kv_bits)
        key = jax.random.PRNGKey(1)
        fill = jax.random.normal(key, pages.k[:, :, 2].shape)
        pages = pages.replace(k=pages.k.at[:, :, 2].set(
            fill.astype(pages.k.dtype)))
        if kv_bits:
            pages = pages.replace(k_scale=pages.k_scale.at[:, :, 2].set(0.5))
        forked = fork_tail_page(pages, jnp.int32(2), jnp.int32(4))
        np.testing.assert_array_equal(np.asarray(forked.k[:, :, 4]),
                                      np.asarray(forked.k[:, :, 2]))
        np.testing.assert_array_equal(np.asarray(forked.v[:, :, 4]),
                                      np.asarray(forked.v[:, :, 2]))
        if kv_bits:
            np.testing.assert_array_equal(
                np.asarray(forked.k_scale[:, :, 4]),
                np.asarray(forked.k_scale[:, :, 2]))


def test_prefix_cache_requires_paged_mode():
    cfg = reduced_f32("mamba2-130m")
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="prefix_cache"):
        ServeEngine(cfg, params, ServeConfig(max_new_tokens=2),
                    n_slots=1, max_len=16, mode="slots", prefix_cache=True)


# ------------------------------------------------ allocator property tests
#
# A random op-sequence drives one PageAllocator + PrefixCache pair; after
# every op the global invariants must hold.  test_sharding_props style:
# ops never corrupt, they only succeed or refuse.

_OPS = st.lists(
    st.tuples(st.sampled_from(["ensure", "free", "insert", "evict",
                               "share"]),
              st.integers(0, 2),            # slot
              st.integers(1, 24)),          # token count / evict count
    min_size=1, max_size=40)


def _check_invariants(alloc, cache):
    assert (alloc.refcount >= 0).all(), "refcount went negative"
    assert alloc.refcount[NULL_PAGE] == 0
    assert NULL_PAGE not in alloc.free, "null page on the free list"
    assert not cache.holds(NULL_PAGE), "null page cached"
    # a page is free XOR mapped/cached; mapped refcount == #mapping slots
    from collections import Counter
    mapped = Counter(p for slot in alloc._mapped for p in slot)
    for page in range(1, alloc.n_pages):
        assert alloc.refcount[page] == mapped.get(page, 0), page
        if page in alloc.free:
            assert alloc.refcount[page] == 0 and not cache.holds(page)
    # no page mapped twice into one slot, none duplicated on the free list
    assert len(alloc.free) == len(set(alloc.free))
    # the incrementally maintained evictable counter always agrees with a
    # from-scratch recount (the pre-incremental full-tree walk)
    assert cache.evictable_count() == cache._recount_evictable(), \
        "incremental evictable counter drifted from the tree recount"


@settings(max_examples=40)
@given(ops=_OPS)
def test_allocator_invariants_under_random_ops(ops):
    alloc = PageAllocator(n_pages=13, page_size=4, n_slots=3, max_len=24)
    cache = PrefixCache(alloc)
    alloc.attach_cache(cache)
    token_streams = [[100 * (s + 1) + i for i in range(24)]
                     for s in range(3)]
    for op, slot, n in ops:
        if op == "ensure":
            alloc.ensure(slot, min(n, 24))
        elif op == "free":
            alloc.free_slot(slot)
        elif op == "insert":
            toks = token_streams[slot][:min(n, 4 * len(
                alloc._mapped[slot]))]
            cache.insert(toks, alloc.block_row(slot))
        elif op == "evict":
            before = {p: int(alloc.refcount[p]) for p in list(
                cache._by_page)}
            cache.evict(n % 4 + 1)
            # eviction only ever touched refcount-0 pages
            gone = set(before) - set(cache._by_page)
            assert all(before[p] == 0 for p in gone), (gone, before)
        elif op == "share":
            m = cache.match(token_streams[slot])
            if m.full_pages and not alloc._mapped[slot]:
                alloc.map_shared(slot, m.full_pages)
        _check_invariants(alloc, cache)


@settings(max_examples=40)
@given(ops=_OPS)
def test_incremental_evictable_counter_matches_recount(ops):
    """The O(1) evictable counter (blocked-subtree bookkeeping adjusted
    on refcount 0<->1 transitions and insert/evict) equals the full-tree
    recount after arbitrary op interleavings — including partial frees,
    shared re-pins of interior pages, and evictions that expose parents.

    Denser than the generic invariant test: two slots intentionally walk
    the *same* token stream so shared pins exercise the 0<->1 hook on
    interior nodes, not only leaves.
    """
    alloc = PageAllocator(n_pages=13, page_size=4, n_slots=3, max_len=24)
    cache = PrefixCache(alloc)
    shared = [300 + i for i in range(24)]
    streams = [shared, shared, [900 + i for i in range(24)]]
    for op, slot, n in ops:
        if op == "ensure":
            alloc.ensure(slot, min(n, 24))
        elif op == "free":
            alloc.free_slot(slot)
        elif op == "insert":
            toks = streams[slot][:min(n, 4 * len(alloc._mapped[slot]))]
            cache.insert(toks, alloc.block_row(slot))
        elif op == "evict":
            cache.evict(n % 4 + 1)
        elif op == "share":
            m = cache.match(streams[slot])
            if m.full_pages and not alloc._mapped[slot]:
                alloc.map_shared(slot, m.full_pages)
        assert cache.evictable_count() == cache._recount_evictable(), \
            (op, slot, n)
    # drain everything: counter must walk back to the empty-tree fixpoint
    for slot in range(3):
        alloc.free_slot(slot)
    cache.evict(cache.cached_pages)
    assert cache.cached_pages == 0
    assert cache.evictable_count() == cache._recount_evictable() == 0


def test_lru_heap_evicts_least_recently_used_first():
    """The lazy heap preserves the old scan's LRU order: a re-matched
    (touched) chain outlives an untouched one under partial eviction."""
    alloc = PageAllocator(n_pages=17, page_size=4, n_slots=2, max_len=32)
    cache = PrefixCache(alloc)
    old_toks = [100 + i for i in range(8)]   # 2 pages, inserted first
    new_toks = [500 + i for i in range(8)]
    alloc.ensure(0, 9)
    cache.insert(old_toks, alloc.block_row(0))
    alloc.free_slot(0)
    alloc.ensure(1, 9)
    cache.insert(new_toks, alloc.block_row(1))
    alloc.free_slot(1)
    old_pages = [cache.match(old_toks + [1]).full_pages,
                 cache.match(new_toks + [1]).full_pages]
    # touch the *old* chain so it becomes most-recently-used
    cache.match(old_toks + [7])
    assert cache.evict(2) == 2
    # the untouched (new) chain died; the touched one survived
    assert all(cache.holds(int(p)) for p in old_pages[0])
    assert not any(cache.holds(int(p)) for p in old_pages[1])


@settings(max_examples=20)
@given(n_tokens=st.integers(1, 24), waves=st.integers(2, 5))
def test_alloc_free_alloc_reuses_pages(n_tokens, waves):
    """Without a cache holding pages resident, free_slot returns every
    page and the next allocation reuses them — the pool never leaks."""
    alloc = PageAllocator(n_pages=9, page_size=4, n_slots=1, max_len=24)
    seen = set()
    for _ in range(waves):
        assert alloc.ensure(0, n_tokens)
        pages = set(alloc._mapped[0])
        assert NULL_PAGE not in pages
        if seen:
            assert pages == seen, "alloc-free-alloc changed the page set"
        seen = pages
        alloc.free_slot(0)
        assert alloc.free_pages == 8
        assert alloc.refcount.sum() == 0


def test_null_page_never_granted_or_freed():
    alloc = PageAllocator(n_pages=5, page_size=2, n_slots=1, max_len=8)
    assert alloc.ensure(0, 8)
    assert NULL_PAGE not in alloc._mapped[0]
    with pytest.raises(ValueError):
        alloc._release_page(NULL_PAGE)
    with pytest.raises(ValueError):
        alloc.map_shared(0, [NULL_PAGE])


def test_deep_chain_does_not_recurse():
    """A long prompt caches as one deep node chain (one node per page);
    the capacity walk must be iterative — 2000 cached pages used to blow
    Python's recursion limit inside admission."""
    alloc = PageAllocator(n_pages=2102, page_size=4, n_slots=1,
                          max_len=8400)
    cache = PrefixCache(alloc)
    alloc.attach_cache(cache)
    assert alloc.ensure(0, 2000 * 4)
    toks = list(range(2000 * 4))
    assert cache.insert(toks, alloc.block_row(0)) == 2000
    alloc.free_slot(0)
    assert cache.evictable_count() == 2000
    m = cache.match(toks)
    assert len(m.full_pages) == 1999 and m.partial[1] == 3
    assert alloc.can_allocate(2100)
    assert cache.evict(2000) == 2000
    assert alloc.free_pages == 2101


def test_eviction_skips_referenced_and_interior_pages():
    """evict() drains leaf-first and never touches a page with live
    references — a shared prefix pins itself and its ancestors."""
    alloc = PageAllocator(n_pages=17, page_size=4, n_slots=2, max_len=32)
    cache = PrefixCache(alloc)
    alloc.attach_cache(cache)
    assert alloc.ensure(0, 12)
    toks = list(range(200, 212))
    cache.insert(toks, alloc.block_row(0))
    row = alloc.block_row(0)
    alloc.free_slot(0)                  # all 3 cached pages refcount 0
    assert cache.evictable_count() == 3

    # re-share the *first* page only: it is pinned; its descendants are
    # still evictable leaves
    alloc.map_shared(1, [int(row[0])])
    assert cache.evictable_count() == 2
    assert cache.evict(10) == 2
    assert cache.holds(int(row[0]))
    assert alloc.refcount[int(row[0])] == 1
    # unpin: now the last page drains too
    alloc.free_slot(1)
    assert cache.evict(10) == 1
    assert cache.cached_pages == 0
