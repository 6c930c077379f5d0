"""Device-idle time put down to the engine step's host spans
(``bench/yardstick/idle.py``), on a hand-made trace whose answers are
known: each share, the nested ``serve.logits_to_host`` counted once, the
shares summing to the device's idle share, and None where the trace has
no such span."""

from types import SimpleNamespace

import pytest

import _tiny  # noqa: F401  (puts the checkout on sys.path)
from bench import harness
from bench.yardstick import idle, readers
from bench.yardstick import trace as T

US = 1000  # ns

# one prefill step and one decode step tiling [0, 92] us of a 100 us
# window; the host is outside every serve.* span over [92, 100]
STEP = [("serve.schedule", 0, 5), ("serve.tables", 5, 8),
        ("serve.prefill_chunk", 8, 30), ("serve.logits_to_host", 20, 30),
        ("serve.lane_update", 30, 35), ("serve.retire", 35, 37),
        ("serve.schedule", 37, 40), ("serve.sample", 40, 60),
        ("serve.tables", 60, 62), ("serve.decode_step", 62, 85),
        ("serve.logits_to_host", 75, 85), ("serve.lane_update", 85, 90),
        ("serve.retire", 90, 92)]


def _trace(drop=()):
    ops0 = [["fusion.1", 10 * US, 22 * US], ["fusion.2", 64 * US, 78 * US]]
    ops1 = [["fusion.1", 12 * US, 20 * US], ["fusion.2", 66 * US, 76 * US]]
    host = [["bench.window", 0, 100 * US]] + [
        [n, s * US, e * US] for n, s, e in STEP if n not in drop]
    return {"devices": [ops0, ops1], "host": host}


def _run(trace):
    return SimpleNamespace(trace=None if trace is None else T.Reduced(trace))


# chip 0 idle [0,10] [22,64] [78,100] = 74 us; chip 1 [0,12] [20,66]
# [76,100] = 82 us.  Per chip: sample 20 / 20; logits [22,30]+[78,85] =
# 15 / [20,30]+[76,85] = 19; bookkeeping 27 / 27; unspanned 8 / 8;
# dispatch outside logits [8,10]+[62,64] = 4 / [8,12]+[62,66] = 8.
KNOWN = {"idle_sample_share": 20.0, "idle_logits_share": 17.0,
         "idle_bookkeeping_share": 27.0, "idle_unspanned_share": 8.0}


@pytest.mark.parametrize("name", sorted(KNOWN))
@pytest.mark.parametrize("variant", ["", ".offline"])
def test_each_metric_file_reads_its_known_share(name, variant):
    run = _run(_trace())
    assert harness.reader(name + variant)(run) == pytest.approx(KNOWN[name])


def test_logits_nested_in_dispatch_is_counted_once():
    red = T.Reduced(_trace())
    in_dispatch = idle.idle_in_pct(red, idle.DISPATCH)
    # chip 0: [8,10] + [22,30] + [62,64] + [78,85] = 19 us; chip 1: 27 us
    assert in_dispatch == pytest.approx(23.0)
    assert idle.dispatch_pct(red) == pytest.approx(6.0)
    assert (idle.dispatch_pct(red) + idle.idle_in_pct(red, idle.LOGITS)
            == pytest.approx(in_dispatch))


def test_shares_and_dispatch_sum_to_device_idle():
    run = _run(_trace())
    total = (idle.sample_pct(run) + idle.logits_pct(run)
             + idle.bookkeeping_pct(run) + idle.unspanned_run_pct(run)
             + idle.dispatch_pct(run.trace))
    assert readers.device_idle_pct(run) == pytest.approx(78.0)
    assert total == pytest.approx(readers.device_idle_pct(run))


def test_missing_span_reads_none():
    run = _run(_trace(drop=("serve.sample",)))
    assert idle.sample_pct(run) is None
    assert idle.logits_pct(run) == pytest.approx(17.0)
    # a trace with the dispatch spans alone, as before the step was tiled
    bare = _run(_trace(drop=("serve.schedule", "serve.tables",
                             "serve.lane_update", "serve.retire",
                             "serve.sample", "serve.logits_to_host")))
    for read in (idle.sample_pct, idle.logits_pct, idle.bookkeeping_pct,
                 idle.unspanned_run_pct):
        assert read(bare) is None
        assert read(_run(None)) is None
    assert idle.dispatch_pct(bare.trace) == pytest.approx(
        idle.idle_in_pct(bare.trace, idle.DISPATCH))
