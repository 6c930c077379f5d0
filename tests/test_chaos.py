"""Fault-tolerant serving: deterministic chaos injection, per-request
failure isolation, and the runtime invariant auditor.

The contract under test: a seeded :class:`~repro.ft.ChaosInjector`
replays *exactly* (same seed -> same fire sequence at every site); a
lane's step fault or non-finite logits quarantines only that request —
within the retry budget the request is requeued recompute-style and its
greedy output is token-identical to a fault-free run — while every
other lane keeps decoding; and ``ServeEngine.audit()`` proves the
allocator / prefix-cache / scheduler bookkeeping after every op, both
on healthy runs (never trips) and against hand-planted corruption
(always trips).
"""

import random

import numpy as np
import pytest

from repro.config.base import EngineConfig, ServeConfig
from repro.ft import ChaosInjector
from repro.models import init_params
from repro.serve import AuditError, ServeEngine, ServeFrontend

from conftest import reduced_f32

from hypothesis import given, settings
from hypothesis import strategies as st

PROMPTS = [[1, 2, 3], [4], [5, 6], [7, 8, 9, 10]]


def _engine(cfg, params, *, chaos=None, max_new=5, n_slots=2, max_len=32,
            prefix_cache=False, **scfg_kw):
    scfg = ServeConfig(max_new_tokens=max_new,
                       engine=EngineConfig(backend="reference"), **scfg_kw)
    return ServeEngine(cfg, params, scfg, n_slots=n_slots, max_len=max_len,
                       mode="paged", page_size=4, prefill_chunk=3,
                       chaos=chaos, prefix_cache=prefix_cache)


def _run(cfg, params, **kw):
    eng = _engine(cfg, params, **kw)
    for p in PROMPTS:
        eng.submit(p)
    done = eng.run()
    return eng, {r.rid: r for r in done}


@pytest.fixture(scope="module")
def model():
    import jax

    cfg = reduced_f32("qwen2.5-3b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def baseline(model):
    cfg, params = model
    _, done = _run(cfg, params, audit=1)
    return {rid: list(r.output) for rid, r in done.items()}


# ------------------------------------------------------------- injector
class TestInjector:
    def test_same_seed_replays_exactly(self):
        a = ChaosInjector(seed=9, rates={"step_fault": 0.3,
                                         "nan_logits": 0.2})
        b = ChaosInjector(seed=9, rates={"step_fault": 0.3,
                                         "nan_logits": 0.2})
        for _ in range(200):
            assert a.fire("step_fault") == b.fire("step_fault")
            assert a.fire("nan_logits") == b.fire("nan_logits")
        assert a.log == b.log
        assert a.pick("step_fault", 7) == b.pick("step_fault", 7)

    def test_sites_are_independent_streams(self):
        """Replay is exact even when *other* sites are consulted a
        different number of times (cross-site call order shifts as the
        engine's schedule shifts)."""
        a = ChaosInjector(seed=9, rates={"step_fault": 0.3})
        b = ChaosInjector(seed=9, rates={"step_fault": 0.3,
                                         "page_grant": 0.5})
        seq_a, seq_b = [], []
        for i in range(100):
            if i % 3 == 0:
                b.fire("page_grant")  # extra consultations on b only
            seq_a.append(a.fire("step_fault"))
            seq_b.append(b.fire("step_fault"))
        assert seq_a == seq_b

    def test_schedule_fires_exact_occurrences(self):
        ch = ChaosInjector(seed=0, schedule={"cancel": {0, 3}})
        fired = [ch.fire("cancel") for _ in range(6)]
        assert fired == [True, False, False, True, False, False]
        assert ch.log == [("cancel", 0), ("cancel", 3)]
        assert ch.fired("cancel") == 2 and ch.fired() == 2

    def test_unknown_site_rejected(self):
        with pytest.raises(ValueError):
            ChaosInjector(rates={"not_a_site": 0.5})
        with pytest.raises(ValueError):
            ChaosInjector(schedule={"bogus": {1}})
        ch = ChaosInjector()
        with pytest.raises(ValueError):
            ch.fire("bogus")

    def test_summary_counts_by_site(self):
        ch = ChaosInjector(seed=1, schedule={"step_fault": {0, 1},
                                             "cancel": {0}})
        for _ in range(3):
            ch.fire("step_fault")
            ch.fire("cancel")
        assert ch.summary() == {"step_fault": 2, "cancel": 1}


# ------------------------------------------------- per-request isolation
class TestIsolation:
    def test_page_grant_faults_token_identity(self, model, baseline):
        """Allocator grant failures force rollbacks and re-admission;
        retired outputs stay token-identical to the fault-free run."""
        cfg, params = model
        ch = ChaosInjector(seed=3, rates={"page_grant": 0.3})
        eng, done = _run(cfg, params, chaos=ch, audit=2,
                         max_request_retries=3)
        assert ch.fired("page_grant") > 0
        for rid, r in done.items():
            if r.finish_reason != "error":
                assert list(r.output) == baseline[rid], rid
        eng.audit()

    def test_nan_retry_preserves_tokens(self, model, baseline):
        """One poisoned dispatch, retry budget available: the victim is
        requeued recompute-style and finishes with identical output."""
        cfg, params = model
        ch = ChaosInjector(seed=5, schedule={"nan_logits": {2}})
        eng, done = _run(cfg, params, chaos=ch, audit=1,
                         max_request_retries=2)
        assert ch.fired("nan_logits") == 1
        assert eng.quarantined == 0
        assert {rid: list(r.output) for rid, r in done.items()} == baseline
        assert any(r.retries == 1 for r in done.values())

    def test_nan_quarantine_isolates_one_request(self, model, baseline):
        """Retry budget zero: exactly one request errors (pages
        released, counted), every other lane's output is untouched."""
        cfg, params = model
        ch = ChaosInjector(seed=5, schedule={"nan_logits": {2}})
        eng, done = _run(cfg, params, chaos=ch, audit=1,
                         max_request_retries=0)
        errs = [r for r in done.values() if r.finish_reason == "error"]
        assert len(errs) == 1 and eng.quarantined == 1
        assert errs[0].cancelled and not errs[0].done
        assert len(done) == len(PROMPTS)  # quarantined rid is returned too
        for rid, r in done.items():
            if r.finish_reason != "error":
                assert list(r.output) == baseline[rid], rid
        assert eng.metrics()["quarantined"] == 1
        # quarantine released everything it held
        assert eng.alloc.refcount.sum() == 0
        eng.audit()

    def test_step_faults_and_preempt_storms(self, model, baseline):
        """Simulated device errors on prefill *and* decode dispatches
        plus mass-eviction storms: recompute recovery keeps identity."""
        cfg, params = model
        ch = ChaosInjector(seed=7, rates={"step_fault": 0.15,
                                          "preempt_storm": 0.1})
        eng, done = _run(cfg, params, chaos=ch, audit=2,
                         max_request_retries=5)
        assert ch.fired("step_fault") > 0
        for rid, r in done.items():
            if r.finish_reason != "error":
                assert list(r.output) == baseline[rid], rid

    def test_quarantine_scrubs_poisoned_pages(self, model):
        """NaN written into a faulted lane's KV pages must not outlive
        the fault: attention masks additively (score + -inf), so a NaN
        in the masked tail of a reused page would poison the *next*
        tenant's softmax.  Quarantine zeroes the lane's private pages
        before the free list gets them back."""
        import jax.numpy as jnp

        cfg, params = model
        clean = _engine(cfg, params, n_slots=1)
        clean.submit([5, 6, 7])
        want = list(clean.run()[0].output)

        eng = _engine(cfg, params, n_slots=1, max_request_retries=0)
        victim = eng.submit([1, 2, 3, 4, 5])
        eng.step()  # prefill lands: slot 0 owns real KV pages
        assert eng.alloc._mapped[0]
        idx = jnp.asarray(eng.alloc._mapped[0], jnp.int32)
        eng.pages = eng.pages.replace(
            k=eng.pages.k.at[:, :, idx].set(jnp.nan),
            v=eng.pages.v.at[:, :, idx].set(jnp.nan))
        eng._fault(0, victim, "nan_logits")  # budget 0 -> quarantine
        assert victim.finish_reason == "error"
        assert np.isfinite(np.asarray(eng.pages.k)).all()
        assert np.isfinite(np.asarray(eng.pages.v)).all()
        eng.audit()
        # the pool is safe to reuse: same tokens as the clean engine
        after = eng.submit([5, 6, 7])
        eng.run()
        assert list(after.output) == want

    def test_frontend_surfaces_error_state(self, model):
        """A quarantined request's stream terminates in state 'error'
        (not cancelled/timed_out); other streams finish normally."""
        cfg, params = model
        ch = ChaosInjector(seed=5, schedule={"nan_logits": {2}})
        eng = _engine(cfg, params, chaos=ch, max_request_retries=0)
        fe = ServeFrontend(eng)
        streams = [fe.submit(p) for p in PROMPTS]
        fe.drain()
        states = [s.state for s in streams]
        assert states.count("error") == 1, states
        assert all(s in ("done", "error") for s in states)


# --------------------------------------------------------------- auditor
class TestAuditor:
    def test_healthy_run_never_trips(self, model):
        cfg, params = model
        eng, _ = _run(cfg, params, audit=2, prefix_cache=True)
        eng.audit()

    def test_catches_refcount_drift(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        eng.submit(PROMPTS[0])
        eng.step()
        eng.audit()
        eng.alloc.refcount[eng.alloc._mapped[0][0]] += 1
        with pytest.raises(AuditError, match="refcount"):
            eng.audit()

    def test_catches_block_table_corruption(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        eng.submit(PROMPTS[0])
        eng.step()
        eng.alloc.block_tables[0, 0] = eng.alloc.free[-1]
        with pytest.raises(AuditError):
            eng.audit()

    def test_catches_leaked_page(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        eng.submit(PROMPTS[0])
        eng.step()
        eng.alloc.free.pop()  # page now in no free list, no lane, no cache
        with pytest.raises(AuditError, match="leaked"):
            eng.audit()

    def test_catches_double_residency(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        eng.submit(PROMPTS[0])
        eng.step()
        req = eng.sched.slot_req[0]
        eng.sched.queue.append(req)
        with pytest.raises(AuditError, match="both queued and resident"):
            eng.audit()

    def test_catches_cache_blocked_drift(self, model):
        cfg, params = model
        eng, _ = _run(cfg, params, prefix_cache=True)
        eng.audit()
        eng.prefix_cache._blocked += 1
        with pytest.raises(AuditError):
            eng.audit()

    def test_audit_on_slots_mode_rejected(self, model):
        cfg, params = model
        scfg = ServeConfig(max_new_tokens=2, audit=1,
                           engine=EngineConfig(backend="reference"))
        with pytest.raises(ValueError, match="audit"):
            ServeEngine(cfg, params, scfg, n_slots=2, max_len=32,
                        mode="slots")


# ------------------------------------------------------------------ soak
class TestSoak:
    """Seeded random-op storm with the auditor after *every* op."""

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10_000))
    def test_random_ops_hold_invariants(self, model, seed):
        cfg, params = model
        rng = random.Random(seed)
        ch = ChaosInjector(seed=seed,
                           rates={"page_grant": 0.05, "step_fault": 0.05,
                                  "nan_logits": 0.05,
                                  "preempt_storm": 0.02})
        eng = _engine(cfg, params, chaos=ch, prefix_cache=True,
                      max_new=4, max_request_retries=1)
        live = []
        for _ in range(30):
            op = rng.random()
            if op < 0.4:
                n = rng.randint(1, 6)
                live.append(eng.submit(
                    [rng.randint(1, cfg.vocab_size - 1)
                     for _ in range(n)]))
            elif op < 0.5 and live:
                eng.cancel(live.pop(rng.randrange(len(live))))
            elif eng.has_work():
                eng.step()
            eng.audit()
        while eng.has_work():
            eng.step()
            eng.audit()
