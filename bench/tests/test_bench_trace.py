"""The trace reduction (``bench/yardstick/trace.py``): busy time as the
union of op intervals, the idle share, kernel time by name, device time
inside host spans, exposed collective time, and idle gaps named by what
the host was doing.  Checked on a hand-made trace whose answers are known
and on a small trace recorded on a TPU v5e."""

import gzip
import json
import os

import pytest

import _tiny  # noqa: F401  (puts the checkout on sys.path)
from bench.yardstick import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000  # ns


def _hand():
    ops0 = [["fusion.1", 10 * US, 30 * US],
            ["paged_attention_decode", 25 * US, 40 * US],   # overlaps
            ["all-reduce.3", 50 * US, 60 * US],
            ["fusion.2", 55 * US, 58 * US],                 # hides 3 us
            ["fusion.3", 90 * US, 120 * US]]                # past window
    ops1 = [["fusion.1", 0, 20 * US],                       # before window
            ["paged_attention_decode", 30 * US, 50 * US]]
    host = [["bench.window", 5 * US, 100 * US],
            ["serve.decode_step", 8 * US, 45 * US],
            ["serve.prefill_chunk", 60 * US, 85 * US]]
    return {"devices": [ops0, ops1], "host": host}


def test_interval_algebra():
    assert T.merge([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]
    assert T.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10),
                                                          (20, 25)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def test_hand_trace():
    r = T.Reduced(_hand())
    assert r.chips == 2
    assert r.window_s == pytest.approx(95e-6)
    # chip 0 busy: [10,40] + [50,60] + [90,100] = 50 us; chip 1: [5,20]
    # + [30,50] = 35 us
    assert r.busy_s() == pytest.approx(42.5e-6)
    assert r.op_time("paged_attention_decode") == pytest.approx(17.5e-6)
    assert r.op_time("no_such_kernel") is None
    # decode span [8,45]: chip 0 busy 30 us, chip 1 12 + 15 = 27 us
    assert r.busy_in("serve.decode_step") == pytest.approx(28.5e-6)
    assert r.span_count("serve.prefill_chunk") == 1
    # all-reduce [50,60] minus fusion.2 [55,58]: 7 us on chip 0, none on 1
    assert r.exposed_collective_s() == pytest.approx(3.5e-6)
    gaps = r.idle_gaps()
    assert gaps[0] == ["serve.prefill_chunk", pytest.approx(30e-6)]
    assert ["host", pytest.approx(5e-6)] in gaps
    names = [n for n, _ in r.top_ops()]
    assert names[0] == "fusion.1"


def test_window_must_be_marked_once():
    t = _hand()
    t["host"] = [h for h in t["host"] if h[0] != "bench.window"]
    with pytest.raises(ValueError):
        T.Reduced(t)


def _recorded():
    with gzip.open(os.path.join(HERE, "data", "v5e_decode_step.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_recorded_trace_busy_matches_a_brute_force_union():
    import numpy as np

    t = _recorded()
    r = T.Reduced(t)
    res = 100  # ns per cell
    n = int((r.hi - r.lo) / res) + 1
    mask = np.zeros(n, bool)
    for _, s, e in t["devices"][0]:
        a = int(max(0, (s - r.lo) / res))
        b = int(min(n, (e - r.lo) / res))
        mask[a:b] = True
    assert r.busy_s() == pytest.approx(mask.sum() * res * 1e-9, rel=2e-3)
    gaps = T.subtract([(r.lo, r.hi)], r.busy[0])
    assert T.length(gaps) * 1e-9 == pytest.approx(r.window_s - r.busy_s())


def test_recorded_trace_kernels_and_spans():
    t = _recorded()
    r = T.Reduced(t)
    dec = [(n, s, e) for n, s, e in t["devices"][0]
           if n.startswith("paged_attention_decode.")]
    assert len(dec) == 36            # one call per layer of the scan
    assert r.op_time("paged_attention_decode") == pytest.approx(
        sum(e - s for _, s, e in dec) * 1e-9)
    inside = r.busy_in("serve.decode_step")
    span = T.length(r.spans["serve.decode_step"]) * 1e-9
    assert 0 < inside <= span
    # the decode kernel runs inside the decode span, on the host's clock
    lo, hi = r.spans["serve.decode_step"][0]
    assert all(lo <= s and e <= hi for _, s, e in dec)
    labels = {g[0] for g in r.idle_gaps()}
    assert labels <= {"host", "serve.decode_step", "serve.prefill_chunk"}
    assert r.exposed_collective_s() is None   # one chip: no collective
