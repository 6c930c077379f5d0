"""The yardstick's FLOPs arithmetic (``bench/yardstick/costs.py``)
against XLA's compiled counts, and its independence from ``max_len``.

XLA's ``cost_analysis`` counts a ``while`` body once, so the compiled
steps here have one layer.  The program computes attention over the
padded view of every lane; with every lane full to ``max_len`` and every
lane active, that padded work is the real work, and the model must agree
with XLA.  With shorter contexts the model credits less and XLA counts
the same: the roofline then shows the padding the program walks.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _tiny
from bench import harness
from bench.yardstick import costs, weights

B, PAGE, NBLK = 4, 8, 4
T = NBLK * PAGE
TOL = 0.05


def _setup(config, window=0):
    from repro.serve.pages import init_kv_pages

    c = _tiny.cell(config, window=window)
    c.cfg["num_hidden_layers"] = 1
    c.cfg["program"]["overrides"]["n_layers"] = 1
    d = c.dims
    mc = harness.program_config(c)
    params = jax.jit(lambda k: weights.params(d, k))(weights.seed_key(1))
    pages = init_kv_pages(mc, B * NBLK + 1, PAGE)
    bt = jnp.arange(1, 1 + B * NBLK, dtype=jnp.int32).reshape(B, NBLK)
    return d, mc, params, pages, bt


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return float(cost["flops"])


@pytest.mark.parametrize("config", ["qwen2.5-3b", "starcoder2-15b-tp4"])
def test_decode_flops_match_compiled(config):
    from repro.models import decode_step_paged

    d, mc, params, pages, bt = _setup(config)
    fn = functools.partial(decode_step_paged, cfg=mc, eng=None,
                           attn_backend="gather")
    # lanes at position T-1 attend all T keys: real work == padded work
    measured = _xla_flops(fn, params, pages, bt,
                          jnp.full((B,), T - 1, jnp.int32),
                          jnp.ones((B,), bool), jnp.zeros((B, 1), jnp.int32))
    modeled, _ = costs.decode_step(d, [T] * B, PAGE)
    assert 1 - TOL <= modeled / measured <= 1 + TOL, (modeled, measured)
    half, _ = costs.decode_step(d, [T // 2] * B, PAGE)
    assert half < modeled


@pytest.mark.parametrize("config", ["qwen2.5-3b", "starcoder2-15b-tp4"])
def test_prefill_flops_match_compiled(config):
    from repro.models import prefill_chunk

    d, mc, params, pages, bt = _setup(config)
    fn = functools.partial(prefill_chunk, cfg=mc, eng=None,
                           attn_backend="gather")
    # a one-token chunk at position T-1 attends all T keys, as the
    # padded view does
    measured = _xla_flops(fn, params, pages, bt,
                          jnp.zeros((B, 1), jnp.int32),
                          jnp.full((B,), T - 1, jnp.int32),
                          jnp.full((B,), T, jnp.int32))
    modeled, _ = costs.prefill_step(d, [(T - 1, 1)] * B, PAGE)
    assert 1 - TOL <= modeled / measured <= 1 + TOL, (modeled, measured)


def test_weight_bytes_are_the_tree_read_per_step():
    d = _tiny.cell("starcoder2-15b-tp4", dtype="bfloat16").dims
    shapes = jax.eval_shape(lambda k: weights.params(d, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    total = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    embed = d.vocab * d.d_model * d.itemsize      # untied: gathered only
    assert costs.weight_bytes(d) == total - embed
    q = _tiny.cell("qwen2.5-3b", dtype="bfloat16").dims
    qs = jax.eval_shape(lambda k: weights.params(q, k),
                        jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert costs.weight_bytes(q) == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(qs))


def test_window_caps_the_keys():
    d = _tiny.cell("starcoder2-15b-tp4", window=24).dims
    assert costs.attn_decode(d, [100], PAGE) == costs.attn_decode(d, [24],
                                                                  PAGE)
    f_long, _ = costs.attn_prefill(d, [(90, 10)], PAGE)
    f_ref, _ = costs.attn_prefill(d, [(30, 10)], PAGE)
    assert f_long == f_ref


def _served_work(max_len):
    """Per-step modelled work of one fixed set of requests served at a
    given ``max_len``, as the harness's tracker records it."""
    from repro.config.base import EngineConfig, ServeConfig
    from repro.serve import ServeEngine

    c = _tiny.cell()
    d = c.dims
    params = jax.jit(lambda k: weights.params(d, k))(weights.seed_key(4))
    scfg = ServeConfig(mode="paged", page_size=8, prefill_chunk=16,
                       prefix_cache=True, sched="budget", step_tokens=36,
                       engine=EngineConfig(attn_backend="gather"))
    eng = ServeEngine(harness.program_config(c), params, scfg, n_slots=4,
                      max_len=max_len)
    tr = harness.Tracker(eng)
    rng = np.random.default_rng(5)
    for n, m in ((20, 5), (37, 3), (9, 7), (50, 4), (12, 6)):
        tr.submit(harness.traffic.Spec(0.0, rng.integers(
            0, d.vocab, n).tolist(), m), 0.0)
    while eng.has_work():
        tr.step()
    return [(costs.decode_step(d, s.decode, 8),
             costs.prefill_step(d, s.prefill, 8)) for s in tr.steps]


def test_padded_max_len_does_not_change_modelled_work():
    a, b = _served_work(64), _served_work(256)
    assert a == b
    assert len(a) > 5
