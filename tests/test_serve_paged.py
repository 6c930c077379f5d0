"""Paged-KV serving subsystem: greedy equivalence with the fixed-slot
engine (the pinning sweep: pages only move bytes, never change tokens),
allocator/free-list behaviour, chunked-prefill numerics, preemption, and
int8 KV pages through the ``EnginePlan.kv_bits`` knob."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import EngineConfig, ServeConfig
from repro.engine import resolve_plan
from repro.models import init_cache, init_params, prefill, prefill_chunk
from repro.serve import PageAllocator, ServeEngine, init_kv_pages, pages_for

from conftest import reduced_f32

PROMPTS = [[1, 2, 3], [4], [5, 6], [7, 8, 9, 10]]


def _gen(cfg, params, prompts, mode, *, max_new=5, n_slots=2, max_len=32,
         engine=None, **kw):
    scfg = ServeConfig(max_new_tokens=max_new,
                       engine=engine or EngineConfig())
    eng = ServeEngine(cfg, params, scfg, n_slots=n_slots, max_len=max_len,
                      mode=mode, **kw)
    for p in prompts:
        eng.submit(p)
    return eng, sorted(eng.run(), key=lambda r: r.rid)


# ---------------------------------------------------------------- sweep
@pytest.mark.parametrize("arch", ["qwen2.5-3b", "gemma3-27b",
                                  "qwen3-moe-235b-a22b", "musicgen-medium"])
def test_paged_matches_slots(arch, rng):
    """kv_bits=0: paged greedy decode is token-identical to fixed slots
    across dense / sliding-window / moe / audio families."""
    cfg = reduced_f32(arch, capacity_factor=8.0)
    params = init_params(cfg, rng)
    _, slots = _gen(cfg, params, PROMPTS, "slots")
    _, paged = _gen(cfg, params, PROMPTS, "paged", page_size=4,
                    prefill_chunk=3)
    assert len(slots) == len(paged) == len(PROMPTS)
    for a, b in zip(slots, paged):
        assert a.output == b.output, (arch, a.rid, a.output, b.output)
        assert b.done


def test_paged_matches_slots_across_slot_counts(rng):
    """Slot-reuse waves (more requests than lanes) and odd chunk/page
    geometry keep token identity."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    _, ref = _gen(cfg, params, PROMPTS, "slots", n_slots=1, max_new=6)
    for n_slots in (1, 2, 3):
        for chunk in (1, 2, 5):
            _, paged = _gen(cfg, params, PROMPTS, "paged", n_slots=n_slots,
                            max_new=6, page_size=4, prefill_chunk=chunk)
            for a, b in zip(ref, paged):
                assert a.output == b.output, (n_slots, chunk, a.rid)


def test_preemption_token_identical(rng):
    """A page pool too small for all residents forces preemption of the
    longest-running request; recompute-resume keeps greedy tokens exact."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    _, ref = _gen(cfg, params, PROMPTS, "slots", n_slots=3, max_len=48,
                  max_new=16)
    eng, paged = _gen(cfg, params, PROMPTS, "paged", n_slots=3, max_len=48,
                      max_new=16, page_size=4, n_pages=14, prefill_chunk=4)
    assert eng.preemptions > 0
    assert any(r.preemptions > 0 for r in paged)
    for a, b in zip(ref, paged):
        assert a.output == b.output, (a.rid, a.output, b.output)


# ------------------------------------------------------------- kv_bits
def test_kv_bits_resolves_to_plan():
    """kv_bits alone enables the engine: the plan carries bits=0 (dense
    weights) and kv_bits=8 — the previously-dead field is live."""
    plan = resolve_plan(EngineConfig(kv_bits=8, backend="reference"))
    assert plan is not None
    assert plan.bits == 0 and plan.kv_bits == 8
    assert resolve_plan(EngineConfig()) is None  # fully-off still disables


def test_kv8_pages_close_to_slots(rng):
    """kv_bits=8: int8 KV pages track the full-precision engine within
    tolerance (first token exact, large majority of the stream agrees)."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    _, ref = _gen(cfg, params, PROMPTS, "slots", max_new=8)
    eng, kv8 = _gen(cfg, params, PROMPTS, "paged", max_new=8, page_size=4,
                    prefill_chunk=3,
                    engine=EngineConfig(kv_bits=8, backend="reference"))
    assert eng.pages.quantized and eng.pages.k.dtype == jnp.int8
    assert all(a.output[0] == b.output[0] for a, b in zip(ref, kv8))
    agree = sum(t1 == t2 for a, b in zip(ref, kv8)
                for t1, t2 in zip(a.output, b.output))
    total = sum(len(a.output) for a in ref)
    assert agree / total > 0.7, (agree, total)


def test_full_imagine_paged_mode(rng):
    """weights int8 bit-plane + int8 KV pages through one plan."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    _, ref = _gen(cfg, params, PROMPTS[:2], "slots", max_new=6)
    eng, quant = _gen(
        cfg, params, PROMPTS[:2], "paged", max_new=6, page_size=4,
        prefill_chunk=3,
        engine=EngineConfig(weight_bits=8, kv_bits=8, backend="reference"))
    assert eng.plan.bits == 8 and eng.plan.kv_bits == 8
    agree = sum(t1 == t2 for a, b in zip(ref, quant)
                for t1, t2 in zip(a.output, b.output))
    total = sum(len(a.output) for a in ref)
    assert agree / total > 0.6, (agree, total)


# ------------------------------------------------- chunked prefill math
def test_prefill_chunk_matches_prefill(rng):
    """Running prefill_chunk to completion (chunk < prompt) reproduces the
    one-shot batched ``prefill`` last-token logits."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    b, s, page = 2, 11, 4
    tokens = jax.random.randint(rng, (b, s), 0, cfg.vocab_size)

    cache = init_cache(cfg, b, max_len=16)
    ref_logits, _ = prefill(params, {"tokens": tokens}, cfg, cache)

    n_blocks = pages_for(16, page)
    pages = init_kv_pages(cfg, b * n_blocks + 1, page)
    alloc = PageAllocator(b * n_blocks + 1, page, b, 16)
    for lane in range(b):
        assert alloc.ensure(lane, s)
    bt, _ = alloc.device_tables()
    for chunk in (3,):
        got = None
        for c0 in range(0, s, chunk):
            n = min(chunk, s - c0)
            tk = jnp.pad(tokens[:, c0:c0 + n], ((0, 0), (0, chunk - n)))
            pos0 = jnp.full((b,), c0, jnp.int32)
            seq = jnp.full((b,), c0 + n, jnp.int32)
            got, pages = prefill_chunk(params, pages, bt, tk, pos0, seq, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref_logits),
                                   rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------- allocator
def test_page_allocator_unit():
    alloc = PageAllocator(n_pages=9, page_size=4, n_slots=3, max_len=16)
    assert alloc.free_pages == 8  # page 0 reserved as null
    assert alloc.can_admit(16 - 1)
    assert not alloc.can_admit(4 * 8)  # beyond pool capacity

    assert alloc.ensure(0, 9)  # 3 pages
    assert alloc.used_pages == 3
    assert (alloc.block_tables[0, :3] > 0).all()
    assert (alloc.block_tables[0, 3:] == 0).all()
    assert alloc.ensure(0, 9)  # idempotent
    assert alloc.used_pages == 3

    assert alloc.ensure(1, 16)  # 4 pages -> 7 of 8 used
    assert alloc.free_pages == 1
    assert not alloc.ensure(2, 8)  # needs 2 pages: dry
    assert alloc.used_pages == 7  # failed ensure allocates nothing
    assert (alloc.block_tables[2] == 0).all()
    assert alloc.ensure(0, 13)  # the last page
    assert alloc.free_pages == 0

    alloc.free_slot(1)
    assert alloc.free_pages == 4
    assert (alloc.block_tables[1] == 0).all() and alloc.pos[1] == 0
    assert alloc.ensure(2, 8)

    with pytest.raises(ValueError):
        alloc.ensure(0, 17)  # > max_len capacity
    with pytest.raises(ValueError):
        PageAllocator(n_pages=3, page_size=4, n_slots=1, max_len=16)


def test_grant_never_leaks_onto_empty_slot():
    """Regression: after one lane's grant preempts another lane's request,
    a grant for the now-empty slot must refuse (not allocate a page onto a
    slot with no resident request — with minimum-size pools the leaked
    page blocked admission forever)."""
    from repro.serve.engine import Request
    from repro.serve.scheduler import PagedScheduler

    alloc = PageAllocator(n_pages=5, page_size=4, n_slots=2, max_len=16)
    sched = PagedScheduler(alloc, chunk=4)
    for rid in range(2):
        req = Request(rid, [1, 2, 3], 8)
        req.prefill_tokens = list(req.prompt)
        sched.submit(req)
    sched.admit()
    assert all(r is not None for r in sched.slot_req)
    # drain the pool: both lanes at a page boundary, free list dry
    assert alloc.ensure(0, 8) and alloc.ensure(1, 8)
    alloc.pos[:] = 8
    assert alloc.free_pages == 0
    # lane 0's grant preempts lane 1 (the earliest other resident)
    assert sched.grant_decode_page(0)
    assert sched.slot_req[1] is None and sched.preemptions == 1
    free_before = alloc.free_pages
    assert not sched.grant_decode_page(1)  # empty slot: refuse, no alloc
    assert alloc.free_pages == free_before
    assert alloc.block_tables[1].sum() == 0


def test_capacity_admission_queues(rng):
    """With a pool smaller than total demand every request still completes
    (admission waits for pages instead of over-committing)."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    eng, done = _gen(cfg, params, PROMPTS + [[11, 12, 13]], "paged",
                     n_slots=4, max_len=32, max_new=6, page_size=4,
                     n_pages=9, prefill_chunk=3)
    assert len(done) == len(PROMPTS) + 1
    assert all(r.done and len(r.output) == 6 for r in done)
    assert eng.alloc.used_pages == 0  # everything reclaimed
    assert eng.alloc.free_pages == 8


def test_zero_token_request_finishes_its_prefill(rng):
    """A ``max_new_tokens=0`` request whose prompt spans several prefill
    chunks stays resident until its last chunk lands, then retires with
    an empty output.  With a host ``_sample_next`` set, the engine keeps
    each landed lane's logits on the host: the request retires holding
    the prompt's last-position logits — the same logits a one-shot
    forward gives."""
    from repro.models import forward

    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    prompt = list(range(1, 12))            # 11 tokens = 4 chunks of 3
    scfg = ServeConfig(max_new_tokens=0)
    eng = ServeEngine(cfg, params, scfg, n_slots=2, max_len=32,
                      mode="paged", page_size=4, prefill_chunk=3)
    eng._sample_next = functools.partial(ServeEngine._sample_next, eng)
    req = eng.submit(prompt)
    other = eng.submit([4, 5], max_new_tokens=3)
    eng.run()
    assert req.done and req.output == [] and req.finish_reason == "length"
    assert other.done and len(other.output) == 3
    want, _ = forward(params, {"tokens": jnp.asarray([prompt])}, cfg)
    np.testing.assert_allclose(req.last_logits, np.asarray(want)[0, -1],
                               rtol=1e-4, atol=1e-4)
