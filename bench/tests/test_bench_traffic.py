"""The traffic generator: every seed gets the same sizes and gaps in
another order, the token ids follow the seed, and the backlog keeps its
quantiles block by block."""

import itertools

import numpy as np

import _tiny  # noqa: F401  (puts the checkout on sys.path)
from bench.yardstick import traffic

MIX = {"arrivals": {"kind": "poisson", "rate_per_s": 3.0},
       "prompt": {"median": 1024, "sigma": 0.8, "min": 128, "max": 3072},
       "output": {"median": 128, "sigma": 0.8, "min": 16, "max": 768}}


def _open(seed):
    return traffic.Generator(MIX, seed, 151936).open_loop(60.0)


def test_seeds_share_sizes_not_order():
    a, b = _open(2 ** 31 + 11), _open(2 ** 40 + 3)
    assert sorted(len(s.prompt) for s in a) == sorted(
        len(s.prompt) for s in b)
    assert sorted(s.max_new for s in a) == sorted(s.max_new for s in b)
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    assert a[0].prompt != b[0].prompt
    assert abs(len(a) - 180) <= 3


def test_same_seed_same_requests():
    a, b = _open(77), _open(77)
    assert [(s.due_s, s.prompt, s.max_new) for s in a] == [
        (s.due_s, s.prompt, s.max_new) for s in b]


def test_lengths_follow_the_mix():
    a = _open(5)
    p = np.array([len(s.prompt) for s in a])
    o = np.array([s.max_new for s in a])
    assert p.min() >= 128 and p.max() <= 3072
    assert 900 <= np.median(p) <= 1150
    assert o.min() >= 16 and o.max() <= 768
    assert np.all(np.diff([s.due_s for s in a]) > 0)


def test_each_segment_is_the_same_work_on_every_seed():
    def window(seed):
        specs = traffic.Generator(MIX, seed, 151936).open_loop(7.0, 20.0,
                                                               5.0)
        assert [s.segment for s in specs] == sorted(s.segment for s in specs)
        w = [s for s in specs if s.segment == 1]
        assert all(7.0 < s.due_s < 27.0 for s in w)
        return sorted(len(s.prompt) for s in w), sorted(
            s.max_new for s in w)

    a, b = window(2 ** 33 + 1), window(12)
    assert a == b and len(a[0]) == 60


def test_backlog_blocks_hold_the_quantiles():
    mix = dict(MIX, arrivals={"kind": "backlog", "queued_per_slot": 2})
    g = traffic.Generator(mix, 3, 1000)
    first = list(itertools.islice(g.backlog(block=64), 128))
    assert sorted(s.max_new for s in first[:64]) == sorted(
        s.max_new for s in first[64:])


def test_backlog_fills_the_lanes_alike_on_every_seed():
    mix = dict(MIX, arrivals={"kind": "backlog", "queued_per_slot": 2})

    def lanes(seed):
        it = traffic.Generator(mix, seed, 1000).backlog(block=32)
        first = list(itertools.islice(it, 32))
        return (sorted(len(s.prompt) for s in first),
                sorted(s.max_new for s in first),
                [s.max_new for s in first])

    a, b = lanes(2 ** 33 + 7), lanes(41)
    assert a[:2] == b[:2]
    assert a[2] != b[2]


def test_shared_prefix_opens_every_prompt():
    mix = dict(MIX, shared_prefix={"tokens": 100, "count": 2})
    reqs = traffic.Generator(mix, 9, 5000).open_loop(10.0)
    heads = {tuple(s.prompt[:100]) for s in reqs}
    assert len(heads) == 2
