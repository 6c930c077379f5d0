"""SLA-aware budget scheduler (``repro.serve.scheduler.BudgetScheduler``).

The three scheduling claims this file pins:

* the per-step token budget (prefill + decode) is a hard invariant —
  chunked-prefill interleaving never exceeds ``step_tokens``;
* decode lanes advance **every** step while a long prompt prefills (the
  budget funds decode first, prefill gets the remainder);
* weighted fair share bounds priority inversion — a ``batch``-class
  request completes within a bounded number of steps no matter how much
  ``interactive`` traffic keeps arriving.

Plus token identity: the budget scheduler reorders *work*, never tokens
(greedy output matches FCFS exactly), and host-side WFQ unit tests.
"""

import pytest

from repro.config.base import EngineConfig, ServeConfig
from repro.models import init_params
from repro.serve import (
    BudgetScheduler,
    PageAllocator,
    Request,
    ServeEngine,
)

from conftest import reduced_f32

PROMPTS = [[1, 2, 3], [4], [5, 6], [7, 8, 9, 10]]


def _engine(cfg, params, *, sched="budget", step_tokens=0, n_slots=2,
            max_len=96, max_new=5, prefill_chunk=4, **kw):
    scfg = ServeConfig(max_new_tokens=max_new, sched=sched,
                       step_tokens=step_tokens,
                       engine=EngineConfig(backend="reference"), **kw)
    return ServeEngine(cfg, params, scfg, n_slots=n_slots, max_len=max_len,
                       mode="paged", page_size=4,
                       prefill_chunk=prefill_chunk)


# -------------------------------------------------------------- identity
def test_budget_output_identical_to_fcfs(rng):
    """Scheduling policy changes latency, never tokens: greedy output
    under the budget scheduler (with priorities mixed in) matches FCFS
    per request."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)

    def gen(sched):
        eng = _engine(cfg, params, sched=sched, step_tokens=6, max_new=6)
        prios = ["batch", "interactive", "default", "interactive"]
        for p, pr in zip(PROMPTS, prios):
            eng.submit(list(p), priority=pr if sched == "budget"
                       else "default")
        return sorted(eng.run(), key=lambda r: r.rid)

    fcfs, budget = gen("fcfs"), gen("budget")
    assert len(fcfs) == len(budget) == len(PROMPTS)
    for a, b in zip(fcfs, budget):
        assert a.output == b.output, (a.rid, a.output, b.output)
        assert b.done and b.finish_reason == "length"


# ---------------------------------------------------------------- budget
def test_per_step_token_budget_never_exceeded(rng):
    """Hard invariant: prefill tokens + decode tokens per engine step
    never exceed ``step_tokens``, across admission waves, long prompts
    and lanes completing prefill mid-step (the +1 completion reserve:
    such a lane's first token comes from its last chunk, and it decodes
    that token in the same step).  Decode tokens are the lanes each
    decode dispatch runs."""
    import numpy as np

    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    step_tokens = 7
    eng = _engine(cfg, params, step_tokens=step_tokens, n_slots=3,
                  max_new=4, prefill_chunk=5)
    decoded = [0]
    dec = eng._decode_paged

    def counted(params, pages, bt, pos, active, *rest):
        decoded[0] += int(np.asarray(active).sum())
        return dec(params, pages, bt, pos, active, *rest)

    eng._decode_paged = counted
    reqs = [eng.submit(list(range(1, 1 + n)), max_new_tokens=4)
            for n in (29, 3, 17, 1, 40, 6)]
    prev_prefill, prev_dec = 0, 0
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        assert steps < 500, "scheduler stopped making progress"
        prefill_now, dec_now = eng.prefill_computed, decoded[0]
        spent = (prefill_now - prev_prefill) + (dec_now - prev_dec)
        assert spent <= step_tokens, \
            f"step {steps} spent {spent} > budget {step_tokens}"
        prev_prefill, prev_dec = prefill_now, dec_now
    assert decoded[0] > 0
    assert all(r.done and len(r.output) == 4 for r in reqs)


def test_small_budget_still_makes_progress(rng):
    """step_tokens=2 (the legal minimum) drains a prompt one token per
    step without deferring the tail forever."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    eng = _engine(cfg, params, step_tokens=2, n_slots=1, max_new=2)
    req = eng.submit(list(range(1, 10)), max_new_tokens=2)
    out = eng.run()
    assert req.done and len(req.output) == 2
    assert len(out) == 1


def test_step_tokens_validation():
    with pytest.raises(ValueError, match="step_tokens"):
        BudgetScheduler(PageAllocator(9, 4, 1, 16), chunk=4, step_tokens=1)
    with pytest.raises(ValueError, match="step_tokens"):
        ServeConfig(step_tokens=-1)
    with pytest.raises(ValueError, match="sched"):
        ServeConfig(sched="wfq")


# ------------------------------------------------- decode never stalls
def test_decode_advances_every_step_during_long_prefill(rng):
    """A lane decoding while a 60-token prompt prefills advances by one
    token on *every* step until it finishes — chunked prefill is sliced
    into the budget's remainder and can never stall active decode."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    eng = _engine(cfg, params, step_tokens=6, n_slots=2, max_new=12,
                  prefill_chunk=8)
    short = eng.submit([1, 2, 3], max_new_tokens=12)
    # let the short request reach decode
    while eng.request_phase(short) != "decoding":
        eng.step()
    long = eng.submit(list(range(100, 160)), max_new_tokens=2)
    overlap_steps = 0
    while long.prefill_pos < len(long.prefill_tokens) and not short.done:
        before = len(short.output)
        eng.step()
        assert len(short.output) == before + 1, \
            "decode lane stalled while the long prompt prefilled"
        overlap_steps += 1
    # the budget (6/step minus 1 decode) genuinely sliced the 60-token
    # prompt across many steps — the claim above wasn't vacuous
    assert overlap_steps >= 8, overlap_steps
    eng.run()
    assert short.done and long.done


# ----------------------------------------------------- fair share bound
def test_low_priority_not_starved_by_interactive_flood(rng):
    """Priority inversion bound: with a sustained interactive flood (a
    fresh arrival whenever the queue drains), a batch-class request
    still completes within a bounded number of steps — WFQ serves an
    active weight-1 key 1/(1+8) of the time, it never zeroes it."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    eng = _engine(cfg, params, step_tokens=6, n_slots=2, max_new=3,
                  prefill_chunk=4)
    batch_req = eng.submit(list(range(1, 9)), max_new_tokens=3,
                           priority="batch", tenant="t-batch")
    flood_done = 0
    steps = 0
    while not batch_req.done:
        # keep interactive pressure up: never let the queue go empty
        while len(eng.sched.queue) < 2:
            eng.submit([100 + steps % 50, 101, 102], max_new_tokens=3,
                       priority="interactive", tenant="t-inter")
        done = eng.step()
        flood_done += len(done)
        steps += 1
        assert steps < 400, \
            f"batch request starved ({flood_done} interactive done)"
    assert batch_req.done and len(batch_req.output) == 3
    # the flood really was alive the whole time (queue never drained) and
    # interactive requests keep completing once the batch lane is done
    assert flood_done >= 1, flood_done
    for _ in range(30):
        if not eng.has_work():
            break
        flood_done += len(eng.step())
    assert flood_done >= 5, flood_done


# ------------------------------------------------------- WFQ unit tests
class _FakeReq:
    def __init__(self, rid, priority="default", tenant="default"):
        self.rid = rid
        self.priority = priority
        self.tenant = tenant
        self.prefill_tokens = [1]
        self.prefill_pos = 0
        self.output = []
        self.max_new_tokens = 1


def _sched(**kw):
    return BudgetScheduler(PageAllocator(33, 4, 2, 32), chunk=4,
                           step_tokens=kw.pop("step_tokens", 8), **kw)


def test_wfq_charge_and_order():
    s = _sched()
    inter = _FakeReq(0, "interactive")
    batch = _FakeReq(1, "batch")
    # equal service advances the batch key 8x faster in virtual time
    s._charge(inter, 8)
    s._charge(batch, 8)
    assert s._vtime[("default", "interactive")] == pytest.approx(1.0)
    assert s._vtime[("default", "batch")] == pytest.approx(8.0)
    assert [r.rid for r in s._service_order([batch, inter])] == [0, 1]
    # fresh keys (unseen tenant) start at the active floor, heavier
    # class wins the tie
    fresh_i = _FakeReq(2, "interactive", "t2")
    fresh_b = _FakeReq(3, "batch", "t2")
    order = s._service_order([fresh_b, fresh_i])
    assert [r.rid for r in order][:2] == [2, 3]


def test_wfq_idle_key_gets_no_banked_credit():
    """A key that sleeps while others are served re-enters at the floor,
    not at its stale (tiny) virtual time — sleeping earns nothing."""
    s = _sched()
    a, b = _FakeReq(0, "default", "a"), _FakeReq(1, "default", "b")
    s._charge(a, 1)          # a barely served, then goes idle
    for _ in range(100):
        s._charge(b, 4)      # b consumes heavily meanwhile
    # keep b active so the floor tracks its virtual time
    s.queue.append(b)
    s._charge(a, 4)          # a returns
    va, vb = s._vtime[("a", "default")], s._vtime[("b", "default")]
    assert va >= vb, (va, vb)  # floor-bumped: no century of banked credit


def test_budget_admission_skips_blocked_head():
    """A queued request that cannot fit does not head-of-line block the
    budget scheduler: later requests that fit are admitted around it
    (FCFS, by contrast, preserves arrival order strictly)."""
    # pool: 8 usable pages, page_size 4 -> a 24-token prompt (7 pages
    # incl. decode token) fits alone but not beside a resident request
    alloc = PageAllocator(9, 4, 2, 32)
    s = BudgetScheduler(alloc, chunk=4, step_tokens=8)
    big = _FakeReq(0)
    big.prefill_tokens = list(range(24))
    small = _FakeReq(1)
    small.prefill_tokens = [1, 2, 3]
    resident = _FakeReq(2)
    resident.prefill_tokens = list(range(8))
    s.queue.extend([big, small])
    # occupy capacity so big (7 pages) cannot fit: resident takes 3 pages
    assert s._try_admit(0, resident)
    s.admit()
    assert s.slot_req[1] is small, "small should be admitted around big"
    assert big in list(s.queue)
