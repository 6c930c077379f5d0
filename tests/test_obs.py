"""Serve-path observability (``repro.obs``).

Pins the subsystem's tentpole claims: the disabled path is a true no-op
(no registry, no clocks, identical tokens with telemetry on/off); the
bounded-bucket histogram's percentile estimate tracks the exact
``benchmarks.common.percentile`` within the owning bucket's width; span
timelines cover every lifecycle path including shed / cancel / preempt /
timeout; exported Chrome traces satisfy the trace-event schema contract
(required keys, consistent B/E nesting per track); and TTFT is measured
per request from its own submit time — the regression this PR fixed,
where mid-run submissions inherited the engine's run start as their
zero point.
"""

import contextlib
import json

import pytest

import repro.obs as obs
from benchmarks.common import percentile as exact_percentile
from repro.config.base import EngineConfig, ServeConfig
from repro.models import init_params
from repro.obs import (
    Histogram,
    MetricsRegistry,
    NULL_TELEMETRY,
    RequestTimeline,
    Telemetry,
    validate_trace,
)
from repro.obs import spans
from repro.obs.telemetry import NullTelemetry
from repro.obs.trace import CACHE_TID, ENGINE_TID, SCHED_TID
from repro.serve import AdmissionRejected, ServeEngine

from conftest import reduced_f32


class ManualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _engine(cfg, params, *, n_slots=2, max_len=64, max_new=4,
            prefix_cache=False, sched="fcfs", clock=None, telemetry=None,
            **scfg_kw):
    scfg = ServeConfig(max_new_tokens=max_new, sched=sched,
                       engine=EngineConfig(backend="reference"), **scfg_kw)
    return ServeEngine(cfg, params, scfg, n_slots=n_slots, max_len=max_len,
                       mode="paged", page_size=4, prefill_chunk=3,
                       prefix_cache=prefix_cache, clock=clock,
                       telemetry=telemetry)


# ------------------------------------------------------------- registry
def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    # create-or-return: same (name, labels) -> same object
    assert reg.counter("reqs_total") is c
    assert reg.counter("reqs_total", reason="shed") is not c
    g = reg.gauge("depth")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2
    # a name is bound to one instrument kind
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")


def test_histogram_percentile_tracks_exact_within_bucket_width():
    """The bounded-bucket estimate vs the exact sorted-sample percentile:
    the error is bounded by the width of the bucket the rank lands in."""
    import random

    rng = random.Random(7)
    h = Histogram("lat", ())
    samples = [rng.uniform(0.0002, 2.0) for _ in range(500)]
    for v in samples:
        h.observe(v)
    for q in (50, 90, 95, 99):
        est = h.percentile(q)
        exact = exact_percentile(samples, q)
        # owning bucket of the exact answer
        import bisect
        i = bisect.bisect_left(h.bounds, exact)
        lo = h.bounds[i - 1] if i > 0 else h.min
        hi = h.bounds[i] if i < len(h.bounds) else h.max
        assert abs(est - exact) <= (hi - lo) + 1e-12, (q, est, exact)
        assert h.min <= est <= h.max


def test_histogram_edges_and_snapshot():
    h = Histogram("lat", (), buckets=(0.1, 1.0))
    for v in (0.05, 0.1, 0.5, 2.0):
        h.observe(v)
    assert h.counts == [2, 1, 1]  # le=0.1 gets both 0.05 and 0.1
    d = h.to_dict()
    assert d["count"] == 4 and d["inf"] == 1
    assert d["min"] == 0.05 and d["max"] == 2.0
    empty = Histogram("none", ())
    assert empty.percentile(50) is None
    assert empty.to_dict()["min"] is None


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("reqs_total", reason="shed").inc(2)
    reg.gauge("pages_free").set(7)
    reg.histogram("lat_s", buckets=(0.1, 1.0)).observe(0.5)
    text = reg.prometheus_text()
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{reason="shed"} 2' in text
    assert "# TYPE pages_free gauge" in text
    assert "# TYPE lat_s histogram" in text
    assert 'lat_s_bucket{le="1.0"} 1' in text
    assert 'lat_s_bucket{le="+Inf"} 1' in text
    assert "lat_s_count 1" in text


# ---------------------------------------------------------------- spans
def test_timeline_lifecycle_and_latency_decomposition():
    tl = RequestTimeline(0, submit_t=1.0)
    tl.transition(spans.ADMITTED, 3.0)
    tl.transition(spans.PREFILLING, 3.0)
    tl.transition(spans.DECODING, 5.0)
    tl.token(5.0)
    tl.token(6.0)
    tl.token(8.0)
    tl.transition(spans.RETIRED, 9.0)
    assert tl.queue_wait == 2.0
    assert tl.ttft == 4.0
    assert tl.tpot == pytest.approx(1.5)  # (8-5)/2
    assert tl.e2e == 8.0
    assert tl.finished and tl.state == spans.RETIRED
    d = tl.to_dict()
    assert d["events"][0] == (spans.SUBMITTED, 1.0)
    assert d["n_tokens"] == 3


def test_timeline_preempt_requeues_and_counts():
    tl = RequestTimeline(1, submit_t=0.0)
    tl.transition(spans.ADMITTED, 1.0)
    tl.transition(spans.PREEMPTED, 2.0)
    assert tl.state == spans.QUEUED  # preemption loops back to queued
    assert tl.n_preemptions == 1
    tl.transition(spans.ADMITTED, 3.0)
    assert tl.queue_wait == 1.0  # first admission wins
    tl.transition(spans.CANCELLED, 4.0)
    assert tl.finished and tl.e2e == 4.0


def test_telemetry_shed_cancel_timeout_paths():
    clk = ManualClock()
    tel = Telemetry(clk, trace=True)
    tel.attach_engine(2, "paged")
    # shed: refused pre-Request — counted by reason, no timeline
    tel.on_shed("queue_full")
    tel.on_shed("deadline")
    assert tel.registry.counter("serve_requests_shed_total",
                                reason="queue_full").value == 1
    assert not tel.timelines
    # cancel vs timeout map to distinct terminal states
    tel.on_submit(0, 4, clk())
    tel.on_submit(1, 4, clk())
    clk.advance(1.0)
    tel.on_cancel(0, "user")
    tel.on_cancel(1, "timed_out")
    assert tel.timelines[0].state == spans.CANCELLED
    assert tel.timelines[1].state == spans.TIMED_OUT
    # preempt path re-queues in the timeline and bumps the counter
    tel.on_submit(2, 4, clk())
    tel.on_admit(2, 0, 0)
    tel.on_preempt(2, 0)
    assert tel.timelines[2].state == spans.QUEUED
    assert tel.registry.counter("serve_preemptions_total").value == 1
    states = tel.snapshot()["request_states"]
    assert states == {spans.CANCELLED: 1, spans.TIMED_OUT: 1,
                      spans.QUEUED: 1}


# ------------------------------------------------------- disabled path
def test_disabled_telemetry_is_noop(rng):
    """With obs off an engine carries the NULL_TELEMETRY singleton: no
    registry, no tracer, no timelines, hooks mutate nothing."""
    assert obs.enabled is False
    assert obs.telemetry() is NULL_TELEMETRY
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    eng = _engine(cfg, params)
    assert eng.obs is NULL_TELEMETRY
    eng.submit([1, 2, 3])
    eng.run()
    assert NULL_TELEMETRY.registry is None
    assert NULL_TELEMETRY.tracer is None
    assert not NULL_TELEMETRY.timelines
    assert NULL_TELEMETRY.snapshot() == {}
    assert NULL_TELEMETRY.export_chrome_trace("/dev/null") is None
    m = eng.metrics()
    assert "obs" not in m and m["submitted"] == 1


def test_tokens_identical_with_telemetry_on_and_off(rng):
    """Observability observes; it never perturbs the greedy tokens."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    prompts = [[1, 2, 3], [4], [5, 6, 7, 8], [2, 2]]

    def serve(tel):
        eng = _engine(cfg, params, telemetry=tel)
        reqs = [eng.submit(list(p)) for p in prompts]
        eng.run()
        return [r.output for r in reqs]

    off = serve(None)
    on = serve(Telemetry(trace=True))
    assert off == on


# ------------------------------------------------------- engine wiring
def test_engine_metrics_and_trace_end_to_end(rng):
    """A real serve run through a live Telemetry: counters line up with
    request facts, the trace validates, and every expected track (engine,
    lanes, scheduler, prefix-cache, pages) carries events."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    tel = Telemetry(trace=True)
    eng = _engine(cfg, params, prefix_cache=True, telemetry=tel, max_new=3)
    prefix = list(range(1, 9))
    r0 = eng.submit(prefix + [9])
    eng.run()
    reqs = [eng.submit(prefix + [20 + i]) for i in range(2)]
    eng.run()

    reg = tel.registry
    assert reg.counter("serve_requests_submitted_total").value == 3
    assert reg.counter("serve_tokens_generated_total").value == sum(
        len(r.output) for r in [r0] + reqs)
    assert reg.counter("prefix_cache_hits_total").value >= 1
    snap = eng.metrics()
    assert snap["obs"]["steps"] > 0
    assert snap["obs"]["request_states"] == {spans.RETIRED: 3}
    assert snap["prefix"]["hit_tokens"] >= 8

    counts = validate_trace(tel.tracer.export())
    pid = tel.tracer.pid
    for tid in (ENGINE_TID, 1, SCHED_TID, CACHE_TID):
        assert counts.get(f"{pid}/{tid}", 0) > 0, f"track {tid} empty"
    names = {(e["tid"], e["name"]) for e in tel.tracer.events}
    assert (1, "prefill") in names and (1, "decode") in names
    assert (SCHED_TID, "admit") in names and (SCHED_TID, "retire") in names
    # per-request timelines carry the full latency decomposition
    tl = tel.timelines[reqs[0].rid].to_dict()
    assert tl["state"] == spans.RETIRED
    assert tl["ttft_s"] is not None and tl["e2e_s"] >= tl["ttft_s"]
    assert tl["cached_tokens"] == 8  # two prefix pages matched


def test_trace_validation_rejects_malformed(tmp_path):
    clk = ManualClock()
    tel = Telemetry(clk, trace=True)
    tel.attach_engine(1, "paged")
    t0 = clk()
    tel.step_begin()
    clk.advance(0.001)
    tel.step_end(t0)
    path = str(tmp_path / "trace.json")
    tel.export_chrome_trace(path)
    with open(path) as f:
        trace = json.load(f)
    for ev in trace["traceEvents"]:
        for k in ("ph", "ts", "pid", "tid", "name"):
            assert k in ev
    validate_trace(trace)

    bad = {"traceEvents": [dict(e) for e in trace["traceEvents"]]}
    del bad["traceEvents"][-1]  # drop the E: unclosed B must fail
    with pytest.raises(ValueError, match="open"):
        validate_trace(bad)
    bad2 = {"traceEvents": [{"ph": "B", "ts": 0, "pid": 1, "tid": 0}]}
    with pytest.raises(ValueError, match="name"):
        validate_trace(bad2)
    bad3 = {"traceEvents": [
        {"ph": "B", "ts": 5.0, "pid": 1, "tid": 0, "name": "a"},
        {"ph": "E", "ts": 1.0, "pid": 1, "tid": 0, "name": "a"},
    ]}
    with pytest.raises(ValueError, match="backwards"):
        validate_trace(bad3)


def test_shed_is_counted_by_reason(rng):
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    tel = Telemetry(trace=False)
    eng = _engine(cfg, params, n_slots=1, sched="budget", max_queue=1,
                  telemetry=tel)
    eng.submit([1, 2, 3])   # queued (depth 1 = max_queue)
    with pytest.raises(AdmissionRejected):
        eng.submit([4, 5, 6])  # queue full -> shed
    shed = [c for (name, _), c
            in tel.registry._counters.items()
            if name == "serve_requests_shed_total"]
    assert sum(c.value for c in shed) == 1
    eng.run()


# -------------------------------------------------- the TTFT regression
def test_ttft_is_per_request_not_run_relative(rng):
    """Regression: a request submitted long after the engine started
    running must get a TTFT measured from *its own* submit time, not
    from the engine's run start (the pre-obs bug gave it the full gap)."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    clk = ManualClock()
    tel = Telemetry(clk, trace=False)
    eng = _engine(cfg, params, n_slots=2, telemetry=tel, clock=clk)

    r1 = eng.submit([1, 2, 3])
    while not r1.output:  # engine mid-run, r1 decoding
        clk.advance(0.01)
        eng.step()
    gap = 10.0
    clk.advance(gap)  # long idle gap before the late arrival
    r2 = eng.submit([4, 5, 6])
    while r2.ttft is None:
        clk.advance(0.01)
        eng.step()
    eng.run()
    # r2's TTFT covers only its own prefill steps, never the 10s gap
    assert r2.ttft < gap / 2, r2.ttft
    assert r1.ttft is not None and r1.ttft < gap / 2
    # the timelines agree with the Request fields
    assert tel.timelines[r2.rid].ttft == pytest.approx(r2.ttft)
    assert tel.registry.histogram("serve_ttft_s").count == 2


# ------------------------------------------------- spans of the paged step
DISPATCH = ("serve.prefill_chunk", "serve.decode_step")
STEP_SPANS = {"serve.schedule", "serve.tables", "serve.prefill_chunk",
              "serve.decode_step", "serve.logits_to_host", "serve.sample",
              "serve.lane_update", "serve.retire"}


class RecordingTelemetry(NullTelemetry):
    """The disabled telemetry with only ``annotate`` overridden, as the
    benchmark's traced runs switch the spans on: records each span with
    the spans open around it."""

    def __init__(self):
        self.opened = []  # (name, names of the spans enclosing it)
        self._stack = []

    def annotate(self, name):
        @contextlib.contextmanager
        def span():
            self.opened.append((name, tuple(self._stack)))
            self._stack.append(name)
            try:
                yield
            finally:
                self._stack.pop()
        return span()


def _serve_counting(eng, prompts):
    """Serve ``prompts`` step by step; returns the span names each step
    opened and the number of prefill and decode dispatches."""
    calls = {"prefill": 0, "decode": 0}

    def counted(kind, fn):
        def call(*a, **kw):
            calls[kind] += 1
            return fn(*a, **kw)
        return call

    eng._prefill_paged = counted("prefill", eng._prefill_paged)
    eng._decode_paged = counted("decode", eng._decode_paged)
    for p in prompts:
        eng.submit(list(p))
    per_step = []
    while eng.has_work():
        n0 = len(eng.obs.opened)
        eng.step()
        per_step.append({n for n, _ in eng.obs.opened[n0:]})
    return per_step, calls


def test_paged_step_is_tiled_by_serve_spans(rng):
    """Every paged step opens its schedule and retire spans, and each
    dispatch its table upload, logits copy and lane update; only the
    logits copy nests, inside a dispatch span, so no span encloses the
    dispatch spans; one ``serve.decode_step`` per decode dispatch."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    rec = RecordingTelemetry()
    eng = _engine(cfg, params, telemetry=rec, max_new=3)
    per_step, calls = _serve_counting(
        eng, [[1, 2, 3, 4, 5, 6, 7], [4], [5, 6, 7, 8]])
    for names in per_step:
        assert {"serve.schedule", "serve.retire"} <= names
        for dispatch in DISPATCH:
            if dispatch in names:
                assert {"serve.tables", "serve.logits_to_host",
                        "serve.lane_update"} <= names
        if "serve.decode_step" in names:
            assert "serve.sample" in names
    assert {n for n, _ in rec.opened} == STEP_SPANS
    for name, outer in rec.opened:
        if name == "serve.logits_to_host":
            assert len(outer) == 1 and outer[0] in DISPATCH
        else:
            assert outer == (), (name, outer)
    count = {n: sum(1 for m, _ in rec.opened if m == n) for n in DISPATCH}
    assert calls["decode"] > 0 and calls["prefill"] > 0
    assert count["serve.decode_step"] == calls["decode"]
    assert count["serve.prefill_chunk"] == calls["prefill"]


@pytest.mark.parametrize("jax_annotations", [False, True])
def test_live_telemetry_puts_step_spans_on_engine_track(rng,
                                                        jax_annotations):
    """A live ``Telemetry(trace=True)`` writes the same span names as
    B/E pairs on the engine track, nested in each step, and the trace
    validates, with or without the profiler annotations."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    tel = Telemetry(trace=True, jax_annotations=jax_annotations)
    eng = _engine(cfg, params, telemetry=tel, max_new=3)
    for p in ([1, 2, 3, 4, 5, 6, 7], [4], [5, 6, 7, 8]):
        eng.submit(p)
    eng.run()
    validate_trace(tel.tracer.export())
    engine = [e for e in tel.tracer.events
              if e["tid"] == ENGINE_TID and e["ph"] in ("B", "E")]
    assert {e["name"] for e in engine} == STEP_SPANS | {"step"}
    assert not any(e["name"] == "memory" for e in tel.tracer.events)
