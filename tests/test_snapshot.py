"""Crash-consistent ``ServeEngine.snapshot()/restore()``.

The recovery drill: run an engine to step *k*, snapshot, keep the
original running to completion, then restore the snapshot into a
*fresh* engine and drain it — greedy outputs must be token-identical,
in-memory and through the ``repro.ckpt`` disk format, across kv_bits
0/8, with prefix-cache state (radix tree, pins, LRU) and the budget
scheduler's virtual-time lanes intact, and on a (4, 2) device mesh
(the pool re-places under the restoring engine's shardings).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.config.base import EngineConfig, ServeConfig
from repro.models import init_params
from repro.serve import ServeEngine

from conftest import reduced_f32

PROMPTS = [[1, 2, 3], [4], [5, 6, 1, 2, 3, 4, 5], [1, 2, 3, 4, 9]]


@pytest.fixture(scope="module")
def model():
    import jax

    cfg = reduced_f32("qwen2.5-3b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, *, kv_bits=0, prefix_cache=False, sched="fcfs",
            max_new=6, n_pages=0):
    scfg = ServeConfig(max_new_tokens=max_new, sched=sched,
                       n_pages=n_pages,
                       engine=EngineConfig(kv_bits=kv_bits,
                                           backend="reference"))
    return ServeEngine(cfg, params, scfg, n_slots=2, max_len=32,
                       mode="paged", page_size=4, prefill_chunk=3,
                       prefix_cache=prefix_cache)


def _submit_all(eng):
    for i, p in enumerate(PROMPTS):
        eng.submit(list(p), priority="interactive" if i % 2 else "batch",
                   tenant=f"t{i % 2}")


def _drain(eng):
    return {r.rid: list(r.output) for r in eng.run()}


# ------------------------------------------------------------- identity
@pytest.mark.parametrize("kv_bits", [0, 8])
@pytest.mark.parametrize("prefix_cache", [False, True])
def test_kill_at_step_k_restore_identical(model, kv_bits, prefix_cache):
    cfg, params = model
    kw = dict(kv_bits=kv_bits, prefix_cache=prefix_cache)

    engA = _engine(cfg, params, **kw)
    _submit_all(engA)
    for _ in range(3):  # mid-prefill / early-decode crash point
        engA.step()
    snap = engA.snapshot()
    ref = _drain(engA)  # the uninterrupted run

    engB = _engine(cfg, params, **kw)  # fresh process stand-in
    engB.restore(snap)
    engB.audit()
    assert _drain(engB) == ref


def test_budget_scheduler_vtime_restored(model):
    """Fair-share virtual time is engine state: dropping it would
    re-order admissions after restore."""
    cfg, params = model
    engA = _engine(cfg, params, sched="budget", prefix_cache=True)
    _submit_all(engA)
    for _ in range(2):
        engA.step()
    snap = engA.snapshot()
    ref = _drain(engA)

    engB = _engine(cfg, params, sched="budget", prefix_cache=True)
    engB.restore(snap)
    assert engB.sched._vtime == engA.sched._vtime or engB.sched._vtime
    assert _drain(engB) == ref


def test_snapshot_excludes_terminal_requests(model):
    cfg, params = model
    eng = _engine(cfg, params)
    done_req = eng.submit([7, 8], max_new_tokens=2)
    eng.run()
    assert done_req.done
    _submit_all(eng)
    eng.step()
    snap = eng.snapshot()
    rids = {r["rid"] for r in snap["host"]["requests"]}
    assert done_req.rid not in rids
    assert len(rids) == len(PROMPTS)


def test_every_pending_state_is_captured(model):
    """Snapshot taken while requests are simultaneously queued,
    mid-chunked-prefill and decoding — each resumes from its exact
    position (prefill_pos, pos, partially generated output)."""
    cfg, params = model
    eng = _engine(cfg, params, prefix_cache=True)
    _submit_all(eng)
    eng.step()  # 2 lanes admitted, 2 queued, prefill chunk 1 done
    snap = eng.snapshot()
    states = {r["rid"]: r for r in snap["host"]["requests"]}
    assert any(r["prefill_pos"] > 0 for r in states.values())
    assert snap["host"]["sched"]["queue"]  # someone still waiting
    ref = _drain(eng)
    engB = _engine(cfg, params, prefix_cache=True)
    engB.restore(snap)
    assert _drain(engB) == ref


def test_snapshot_without_pending_ids_resumes(model):
    """A snapshot in the format from before the engine sampled inside its
    step holds a ready lane's logits and no pending id (the newest id not
    yet drawn).  Restore draws that id from the logits, so the drained
    outputs equal the uninterrupted run's."""
    import functools

    cfg, params = model
    engA = _engine(cfg, params)
    # the host path keeps each ready lane's logits, as that format did
    engA._sample_next = functools.partial(ServeEngine._sample_next, engA)
    _submit_all(engA)
    for _ in range(3):
        engA.step()
    snap = engA.snapshot()
    ref = _drain(engA)
    ready = 0
    for r in snap["host"]["requests"]:
        pending = r.pop("pending")
        if pending is not None:
            assert r["output"].pop() == pending
            assert f"logits/{r['rid']}" in snap["arrays"]
            ready += 1
    assert ready
    engB = _engine(cfg, params)
    engB.restore(snap)
    engB.audit()
    assert _drain(engB) == ref


# ------------------------------------------------------------------ disk
def test_disk_roundtrip_and_latest(model, tmp_path):
    cfg, params = model
    engA = _engine(cfg, params, prefix_cache=True)
    _submit_all(engA)
    engA.step()
    engA.save_snapshot(str(tmp_path), 1)
    for _ in range(2):
        engA.step()
    engA.save_snapshot(str(tmp_path), 3)
    ref = _drain(engA)

    engB = _engine(cfg, params, prefix_cache=True)
    assert engB.load_snapshot(str(tmp_path)) == 3  # latest committed
    engB.audit()
    assert _drain(engB) == ref

    engC = _engine(cfg, params, prefix_cache=True)
    assert engC.load_snapshot(str(tmp_path), step=1) == 1
    assert _drain(engC) == ref


def test_geometry_mismatch_rejected(model, tmp_path):
    cfg, params = model
    engA = _engine(cfg, params)
    _submit_all(engA)
    engA.step()
    snap = engA.snapshot()

    engB = _engine(cfg, params, kv_bits=8)
    with pytest.raises(ValueError, match="geometry"):
        engB.restore(snap)
    engC = _engine(cfg, params, n_pages=64)
    with pytest.raises(ValueError, match="geometry"):
        engC.restore(snap)

    engA.save_snapshot(str(tmp_path), 0)
    # a non-snapshot checkpoint directory is refused up front
    from repro.ckpt import save_checkpoint

    other = tmp_path / "train"
    save_checkpoint(str(other), 5, {"w": np.zeros((2, 2), np.float32)})
    engD = _engine(cfg, params)
    with pytest.raises(ValueError, match="snapshot"):
        engD.load_snapshot(str(other))


def test_snapshot_is_a_copy_not_a_view(model):
    """Stepping the engine after snapshot() must not mutate the taken
    snapshot (donated buffers!) — the drill depends on it."""
    cfg, params = model
    eng = _engine(cfg, params)
    _submit_all(eng)
    eng.step()
    snap = eng.snapshot()
    k_before = snap["arrays"]["pages/k"].copy()
    eng.run()
    np.testing.assert_array_equal(snap["arrays"]["pages/k"], k_before)


# ------------------------------------------------------------------ mesh
def test_recovery_drill_on_mesh():
    """(4, 2) forced-host mesh: snapshot on-mesh, restore into a fresh
    on-mesh engine (pool re-placed under its shardings) — greedy
    outputs token-identical to the uninterrupted sharded run."""
    pre = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, "src"); sys.path.insert(0, "tests")
        import jax
        from conftest import reduced_f32
        from repro.config.base import EngineConfig, ServeConfig
        from repro.dist import make_mesh
        from repro.models import init_params
        from repro.serve import ServeEngine

        cfg = reduced_f32("qwen2.5-3b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompts = [[1, 2, 3], [4], [5, 6, 1, 2, 3, 4, 5], [1, 2, 3, 4, 9]]
        mesh = make_mesh((4, 2), ("data", "model"))

        def engine(kv_bits):
            scfg = ServeConfig(max_new_tokens=6, engine=EngineConfig(
                kv_bits=kv_bits, backend="reference"))
            return ServeEngine(cfg, params, scfg, n_slots=2, max_len=32,
                               mode="paged", page_size=4, prefill_chunk=3,
                               prefix_cache=True, mesh=mesh)

        for kv in (0, 8):
            a = engine(kv)
            for p in prompts:
                a.submit(list(p))
            for _ in range(3):
                a.step()
            snap = a.snapshot()
            ref = {r.rid: r.output for r in a.run()}

            b = engine(kv)
            b.restore(snap)
            b.audit()
            kspec = b.pages.k.sharding.spec
            assert "data" in str(kspec) and "model" in str(kspec), kspec
            got = {r.rid: r.output for r in b.run()}
            assert got == ref, (kv, got, ref)
            print("kv", kv, "mesh recovery drill identical")
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", pre], capture_output=True,
                         text=True, cwd=repo, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
