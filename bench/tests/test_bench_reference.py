"""The float32 reference of ``bench/yardstick`` against the program, at
tiny widths on the CPU.

In float32 the served path (chunked paged prefill, paged decode, the LM
head, all through ``ServeEngine``) and the reference agree to rounding,
so greedy tokens are the reference's own best: every gap is 0 to
rounding and the check passes.  The program's own lower-precision paths
(bfloat16 everywhere; int8 weights and KV pages, the control) fail the
same limit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _tiny
from bench import harness
from bench.yardstick import reference, weights

CASES = [("qwen2.5-3b", 0), ("starcoder2-15b-tp4", 0),
         ("starcoder2-15b-tp4", 24)]


def test_reference_layer_weights_are_the_served_weights():
    d = _tiny.cell(dtype="bfloat16").dims
    key = weights.seed_key(2 ** 33 + 1)
    stacked = jax.jit(lambda k: weights.params(d, k))(key)
    for i in range(d.n_layers):
        one = jax.jit(lambda k, j: weights.layer(d, k, j))(key, jnp.int32(i))
        for a, b in zip(jax.tree.leaves(one),
                        jax.tree.leaves(stacked["layers"])):
            assert a.dtype == jnp.bfloat16
            assert bool(jnp.array_equal(a, b[i]))


@pytest.mark.parametrize("config,window", CASES)
def test_reference_logits_match_program_forward(config, window):
    from repro.models import forward

    c = _tiny.cell(config, window=window)
    d = c.dims
    key = weights.seed_key(3)
    params = jax.jit(lambda k: weights.params(d, k))(key)
    seq = np.random.default_rng(0).integers(0, d.vocab, 48).tolist()
    got, _ = forward(params, {"tokens": jnp.asarray([seq])},
                     harness.program_config(c), None)
    got = np.asarray(got[0], np.float32)
    # served token j is predicted at position j: compare the gap of the
    # program's own argmax at every position
    served = [int(t) for t in got.argmax(-1)]
    gaps = reference.served_gaps(d, 3, [(seq[:1], served[:1])],
                                 tokens=64, rows=64, q_block=64)
    assert gaps.shape == (1,)
    full = reference.served_gaps(
        d, 3, [(seq[:1], seq[1:] + [served[-1]])], tokens=64, rows=64,
        q_block=64)
    # the program's logit of the true next token vs the reference's
    tgt = seq[1:] + [served[-1]]
    prog_gap = got.max(-1) - got[np.arange(len(seq)), tgt]
    np.testing.assert_allclose(full, prog_gap, atol=1e-4)


@pytest.mark.parametrize("config,window", CASES)
def test_served_tokens_agree_with_reference(config, window, monkeypatch):
    out = _tiny.run(_tiny.cell(config, window=window), monkeypatch)
    gap = out["checks"]["mean_gap"]
    assert out["checks"]["served_compared"]["value"] >= 10
    assert gap["value"] <= gap["limit"], gap
    assert out["correct"]


@pytest.mark.parametrize("kind", ["w8kv8", "bfloat16"])
def test_lower_precision_fails_the_limit(kind, monkeypatch):
    if kind == "w8kv8":
        # the control reads the program's own served positions
        out = _tiny.run(_tiny.cell(), monkeypatch, control="w8kv8")
        assert out["correct"], out["checks"]
        ctl = out["control"]
        assert (ctl["checks"]["served_compared"]["value"]
                == out["checks"]["served_compared"]["value"])
        checks, correct = ctl["checks"], ctl["correct"]
    else:
        out = _tiny.run(_tiny.cell(dtype="bfloat16"), monkeypatch)
        checks, correct = out["checks"], out["correct"]
    gap = checks["mean_gap"]
    assert gap["value"] > gap["limit"], checks
    assert not correct


def test_forced_program_puts_its_own_tokens_first():
    """Fed its own served tokens, the program as configured puts each of
    them first again: the forced control reads the served positions."""
    c = _tiny.cell()
    d = c.dims
    params = jax.jit(lambda k: weights.params(d, k))(weights.seed_key(8))
    eng = harness.build_engine(c, params, None, trace=False, control=None)
    rng = np.random.default_rng(8)
    reqs = [eng.submit(rng.integers(0, d.vocab, n).tolist(), m)
            for n, m in ((30, 9), (5, 14), (17, 4), (41, 11), (12, 7))]
    while eng.has_work():
        eng.step()
    served = [(r.prompt, r.output) for r in reqs]
    firsts = harness.teacher_forced(c, 8, served, None, log=lambda m: None)
    assert firsts == [list(s) for _, s in served]


def test_sample_keeps_the_longest_and_fits_the_budget():
    fin = [([1] * n, [2] * 3) for n in (5, 40, 12, 30, 7)]
    chosen = reference.sample(fin, 9, tokens=60, rows=64)
    assert chosen[0] == fin[1]
    assert reference.fits(chosen, 60, 64)
    assert reference.sample(fin, 9, 60, 64) == chosen
