"""Sampling inside the jitted paged step.

Each paged dispatch draws every lane's next id from the logits it just
computed and hands the host ``(B,)`` ids and finite flags; a
``_sample_next`` override turns on the host path (a copy of the logits,
the override called once per lane per token).  Pinned here:

* greedy ids from the device equal the host path's, token for token,
  under the budget scheduler, preemption and a prefix-cache hit — on one
  device and on a four-device ``(1, 4)`` mesh, where the vocabulary is
  sharded;
* temperature + top-k draws stay inside each lane's top k and replay
  from the seed;
* a non-finite lane or a step fault (chaos) faults that lane only;
* ``serve_tokens_sampled_total{where=...}`` says which path drew each
  token.
"""

import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import EngineConfig, ServeConfig
from repro.ft import ChaosInjector
from repro.models import init_params
from repro.obs import Telemetry
from repro.serve import ServeEngine
from repro.serve.sampler import sample, sample_rows

from conftest import reduced_f32, serve_logged

SHARED = list(range(20, 33))  # 13 tokens: three full pages of 4, and one
CASES = {
    # chunked prefill shares steps with decode under a token budget
    "budget_mixed": dict(
        prompts=[[1, 2, 3], list(range(40, 70)), [4], [5, 6, 7, 8, 9]],
        scfg=dict(sched="budget", step_tokens=6, max_new_tokens=8),
        eng=dict(n_slots=3, max_len=64, page_size=4, prefill_chunk=4)),
    # a pool too small for every resident: recompute-style preemption
    "preemption": dict(
        prompts=[[1, 2, 3], [4], [5, 6], [7, 8, 9, 10]],
        scfg=dict(max_new_tokens=16),
        eng=dict(n_slots=3, max_len=48, page_size=4, n_pages=14,
                 prefill_chunk=4)),
    # later prompts open with an earlier one's pages
    "prefix_hit": dict(
        prompts=[SHARED + [1], SHARED + [2, 3], SHARED + [4], [9, 8]],
        scfg=dict(max_new_tokens=6, prefix_cache=True),
        eng=dict(n_slots=2, max_len=48, page_size=4, prefill_chunk=4)),
}


@pytest.fixture(scope="module")
def model():
    cfg = reduced_f32("qwen2.5-3b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def host_sampler(eng):
    """The engine's own host sampler set as an override: the host path."""
    eng._sample_next = functools.partial(ServeEngine._sample_next, eng)
    return eng


def serve_case(cfg, params, case, *, hook, mesh=None, telemetry=None):
    """Serve one of :data:`CASES`; returns the engine, each request's
    output in submission order, and the dispatch kinds of every step."""
    c = CASES[case]
    scfg = ServeConfig(engine=EngineConfig(), **c["scfg"])
    eng = ServeEngine(cfg, params, scfg, mode="paged", mesh=mesh,
                      telemetry=telemetry, **c["eng"])
    if hook:
        host_sampler(eng)
    kinds = {}

    def counted(kind, fn):
        def call(*a):
            kinds.setdefault(eng._engine_step, set()).add(kind)
            return fn(*a)
        return call

    eng._prefill_paged = counted("prefill", eng._prefill_paged)
    eng._decode_paged = counted("decode", eng._decode_paged)
    reqs = [eng.submit(p) for p in c["prompts"]]
    eng.run()
    assert all(r.done and r.finish_reason == "length" for r in reqs)
    return eng, [list(r.output) for r in reqs], kinds


def check_case(case, eng, kinds):
    """The case really exercised what it is named for."""
    if case == "budget_mixed":
        assert any(k == {"prefill", "decode"} for k in kinds.values())
    elif case == "preemption":
        assert eng.preemptions > 0
    else:
        assert eng.prefix_stats()["hits"] >= 1


def sampled(tel, where):
    return tel.registry.counter("serve_tokens_sampled_total",
                                where=where).value


# ------------------------------------------------------------ identity
@pytest.mark.parametrize("case", sorted(CASES))
def test_greedy_device_ids_match_host_path(model, case):
    """Greedy: the ids drawn inside the step equal, token for token, what
    the per-lane host sampler draws from a copy of the same logits; the
    counter puts every device token under ``device`` and none under
    ``host``, and the other way round with the override set."""
    cfg, params = model
    tels = {hook: Telemetry(trace=False) for hook in (False, True)}
    runs = {hook: serve_case(cfg, params, case, hook=hook,
                             telemetry=tels[hook])
            for hook in (False, True)}
    (eng, dev, kinds), (_, host, _) = runs[False], runs[True]
    check_case(case, eng, kinds)
    assert dev == host
    total = sum(map(len, dev))
    assert total > 0
    assert (sampled(tels[False], "device"), sampled(tels[False], "host")) \
        == (total, 0)
    assert (sampled(tels[True], "device"), sampled(tels[True], "host")) \
        == (0, total)


def test_greedy_device_ids_match_host_path_on_mesh():
    """The same three cases on a (data=1, model=4) mesh of CPU devices:
    the logits come out sharded over the vocabulary, the ids replicated,
    and the ids equal the host path's."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path[:0] = ["src", "tests"]
        import jax
        from conftest import reduced_f32
        from repro.dist import make_mesh
        from repro.models import init_params
        import test_device_sampling as t

        cfg = reduced_f32("qwen2.5-3b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        mesh = make_mesh((1, 4), ("data", "model"))
        for case in sorted(t.CASES):
            eng, dev, kinds = t.serve_case(cfg, params, case, hook=False,
                                           mesh=mesh)
            t.check_case(case, eng, kinds)
            _, host, _ = t.serve_case(cfg, params, case, hook=True,
                                      mesh=mesh)
            assert dev == host, (case, dev, host)
            print(case, "ok", sum(map(len, dev)))
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=repo, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.count(" ok ") == len(CASES), out.stdout


# ---------------------------------------------------------- temperature
def test_temperature_top_k_draws_inside_top_k_and_replay(model):
    """``temperature > 0, top_k > 0``: every served id lies inside the
    top k of the logits it was drawn from (read back by teacher-forcing a
    host-path engine on the served ids), the same seed serves the same
    ids, and another seed serves others."""
    cfg, params = model
    k = 3
    prompts = [[1, 2, 3], [4], [5, 6, 7, 8, 9], [10, 11]]

    def engine(seed, **kw):
        scfg = ServeConfig(max_new_tokens=10, engine=EngineConfig(), **kw)
        return ServeEngine(cfg, params, scfg, n_slots=2, max_len=48,
                           mode="paged", page_size=4, prefill_chunk=3,
                           seed=seed)

    def drawn(seed):
        eng = engine(seed, temperature=1.0, top_k=k)
        reqs = [eng.submit(p) for p in prompts]
        eng.run()
        return {r.rid: list(r.output) for r in reqs}

    first = drawn(7)
    assert drawn(7) == first
    assert drawn(8) != first
    forced, logits = serve_logged(engine(0), prompts, forced=first)
    assert forced == first
    greedy = 0
    for rid, ids in first.items():
        assert len(ids) == 10
        for j, tok in enumerate(ids):
            row = logits[rid][j]
            assert row[tok] >= np.sort(row)[-k], (rid, j)
            greedy += int(tok == int(np.argmax(row)))
    assert greedy < sum(map(len, first.values()))  # not just argmax


def test_sample_top_k_stays_inside_each_lane():
    """The batched sampler alone: over many keys every draw is one of its
    lane's top k, and each lane draws more than one id."""
    logits = jax.random.normal(jax.random.PRNGKey(1), (6, 50)) * 3
    top = np.argsort(-np.asarray(logits), axis=-1)[:, :4]
    draws = np.stack([np.asarray(sample(logits, jax.random.PRNGKey(i),
                                        temperature=1.5, top_k=4))
                      for i in range(40)])
    assert draws.shape == (40, 6) and draws.dtype == np.int32
    for lane in range(6):
        assert set(draws[:, lane]) <= set(top[lane])
        assert len(set(draws[:, lane])) > 1


@pytest.mark.parametrize("shape", [(4, 1, 32), (4, 1, 3, 32)])
def test_sample_rows_greedy_ids_and_finite_flags(shape):
    """A dispatch's last rows: greedy ids are the argmax (of codebook 0
    for audio's ``(K, V)`` rows), and a lane with any NaN or infinite
    logit, in any codebook, reads not finite."""
    logits = np.array(jax.random.normal(jax.random.PRNGKey(2), shape))
    logits[1, -1, ..., 5] = np.nan
    logits[2, -1].flat[-1] = np.inf  # the last codebook's, for audio
    ids, finite = sample_rows(jnp.asarray(logits), jax.random.PRNGKey(0))
    rows = logits[:, -1, 0] if len(shape) == 4 else logits[:, -1]
    np.testing.assert_array_equal(np.asarray(finite),
                                  [True, False, False, True])
    for lane in (0, 3):
        assert int(ids[lane]) == int(np.argmax(rows[lane]))


# ---------------------------------------------------------------- faults
@pytest.mark.parametrize("site", ["nan_logits", "step_fault"])
def test_chaos_faults_only_the_victim_lane(model, site):
    """One injected fault on a dispatch (a lane's finite flag cleared, or
    a simulated device error) requeues that lane's request and no other;
    every request still serves the fault-free greedy output."""
    cfg, params = model
    prompts = [[1, 2, 3], [4], [5, 6], [7, 8, 9, 10]]

    def run(chaos):
        tel = Telemetry(trace=False)
        scfg = ServeConfig(max_new_tokens=5, max_request_retries=2,
                           engine=EngineConfig(), audit=1)
        eng = ServeEngine(cfg, params, scfg, n_slots=2, max_len=32,
                          mode="paged", page_size=4, prefill_chunk=3,
                          chaos=chaos, telemetry=tel)
        reqs = [eng.submit(p) for p in prompts]
        eng.run()
        return eng, reqs, tel

    _, base, _ = run(None)
    ch = ChaosInjector(seed=5, schedule={site: {4}})
    eng, reqs, tel = run(ch)
    assert ch.fired(site) == 1
    assert sorted(r.retries for r in reqs) == [0, 0, 0, 1]
    assert eng.quarantined == 0
    assert tel.registry.counter("serve_faults_total", kind=site).value == 1
    assert [r.output for r in reqs] == [r.output for r in base]
    total = sum(len(r.output) for r in reqs)
    assert sampled(tel, "device") == total and sampled(tel, "host") == 0


# --------------------------------------------------------------- counter
def test_slots_mode_counts_host_samples(model):
    """Slots mode keeps the host sampler: every token counts as
    ``host``."""
    cfg, params = model
    tel = Telemetry(trace=False)
    eng = ServeEngine(cfg, params, ServeConfig(max_new_tokens=4),
                      n_slots=2, max_len=32, mode="slots", telemetry=tel)
    reqs = [eng.submit(p) for p in ([1, 2, 3], [4], [5, 6])]
    eng.run()
    total = sum(len(r.output) for r in reqs)
    assert total == 12
    assert (sampled(tel, "device"), sampled(tel, "host")) == (0, total)
    assert tel.registry.counter(
        "serve_tokens_generated_total").value == total
