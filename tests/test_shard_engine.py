"""Mesh-native engine: plan resolution with mesh/partition fields, the
``sharded`` backend's bit-for-bit equivalence with its wrapped
single-device backend across the bits × radix sweep, and paged serving on
a (data, model) mesh (token-identical to the unsharded paged engine,
including under preemption).

Multi-device pieces run in a subprocess with 8 forced host devices (the
test_dist pattern), so this process's single-device view is untouched.

The equivalence sweep uses *integer-grid* data (integer activations,
weights that quantize to integers times a power-of-two scale): every fp32
product and partial sum is then exact, so column-parallel reassembly AND
row-parallel ``psum`` reduction are bit-identical to the single-device
accumulation — "bit-for-bit (fp32 accumulate)" is literal, not a
tolerance.
"""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import EngineConfig
from repro.engine import (
    EnginePlan,
    pack_linear,
    partition_kind,
    resolve_plan,
)


def _run_sub(code: str):
    pre = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, "src")
        sys.path.insert(0, "tests")
        import jax, jax.numpy as jnp
        import numpy as np

        def grid_data(b, k, n, bits, seed=0):
            '''Integer-grid (w, x): quantizes exactly, scale = 2^-3.'''
            qmax = 2 ** (bits - 1) - 1
            rng = np.random.default_rng(seed)
            q = rng.integers(-qmax, qmax + 1, (k, n)).astype(np.float32)
            q[0, :] = qmax   # pin per-column absmax -> scale exactly 2^-3
            w = jnp.asarray(q * 2.0 ** -3)
            x = jnp.asarray(rng.integers(-8, 9, (b, k)).astype(np.float32))
            return w, x
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", pre + textwrap.dedent(code)],
                         capture_output=True, text=True, cwd=repo,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# plan resolution (single device — no mesh required)
# ---------------------------------------------------------------------------


def test_sharded_plan_from_config():
    """EngineConfig.sharded wraps the named backend: the plan's backend is
    'sharded' and the config's backend becomes the inner backend."""
    plan = resolve_plan(EngineConfig(weight_bits=8, backend="reference",
                                     sharded=True, psum_bits=8))
    assert plan.backend == "sharded"
    assert plan.inner_backend == "reference"
    assert plan.psum_bits == 8
    assert plan.mesh is None  # resolution without a mesh is legal
    # memoized on (cfg, backend, mesh)
    again = resolve_plan(EngineConfig(weight_bits=8, backend="reference",
                                      sharded=True, psum_bits=8))
    assert plan is again


def test_sharded_plan_validation():
    with pytest.raises(KeyError):
        EnginePlan(backend="sharded", bits=8, inner_backend="no_such")
    with pytest.raises(ValueError):
        EnginePlan(backend="sharded", bits=8, inner_backend="sharded")
    with pytest.raises(ValueError):
        EnginePlan(backend="reference", bits=8, psum_bits=5)
    with pytest.raises(ValueError):
        EngineConfig(psum_bits=3)


def test_sharded_degrades_without_mesh():
    """No mesh on the plan -> the wrapped backend runs unsharded,
    bit-identically (degrade-to-replication, never an error)."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((64, 16)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((2, 64)).astype(np.float32))
    lin = pack_linear(w, 8)
    y_ref = EnginePlan(backend="reference", bits=8).apply(
        lin, x, out_dtype=jnp.float32)
    y_sh = EnginePlan(backend="sharded", bits=8,
                      inner_backend="reference").apply(
        lin, x, out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_sh))


def test_partition_kind_rules():
    rng = np.random.default_rng(1)

    def lin(k, n, bits=8):
        return pack_linear(
            jnp.asarray(rng.standard_normal((k, n)).astype(np.float32)),
            bits)

    assert partition_kind(lin(128, 64), 8) == "col"
    assert partition_kind(lin(128, 20), 8) == "row"   # N not divisible
    assert partition_kind(lin(100, 20), 8) == "replicate"  # neither
    assert partition_kind(lin(128, 64), 1) == "replicate"  # trivial mesh
    # stacked experts stay replicated at this layer (expert-parallelism
    # is the param-spec layer's job)
    stacked = pack_linear(jnp.asarray(
        rng.standard_normal((4, 64, 64)).astype(np.float32)), 8)
    assert partition_kind(stacked, 8) == "replicate"


def test_partition_preference_follows_weight_name():
    """quantize_params stamps the dist.sharding placement into the weight:
    wo/w_down prefer row-parallel even when both axes divide (a weight
    placed P('model', None) must not be re-gathered column-parallel inside
    every decode step), wq/w_up prefer col; the preference yields when its
    axis does not divide."""
    import jax

    from conftest import reduced_f32
    from repro.models import init_params, quantize_params

    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal((128, 64)).astype(np.float32))
    assert partition_kind(pack_linear(w, 8, partition="row"), 8) == "row"
    assert partition_kind(pack_linear(w, 8, partition="col"), 8) == "col"
    assert partition_kind(pack_linear(w, 8), 8) == "col"   # auto
    # preference yields when non-divisible: (100, 64) cannot row-split
    w2 = jnp.asarray(rng.standard_normal((100, 64)).astype(np.float32))
    assert partition_kind(pack_linear(w2, 8, partition="row"), 8) == "col"

    cfg = reduced_f32("qwen2.5-3b")
    q = quantize_params(init_params(cfg, jax.random.PRNGKey(0)), cfg, 8)
    attn = q["layers"]["attn"]
    assert attn["wq"].partition == "col"
    assert attn["wo"].partition == "row"
    assert q["layers"]["mlp"]["w_down"].partition == "row"
    # the preference is static metadata: survives tree ops and scan slices
    sliced = jax.tree.map(lambda a: a[0], attn["wo"])
    assert sliced.partition == "row"


# ---------------------------------------------------------------------------
# the equivalence sweep (8 forced host devices)
# ---------------------------------------------------------------------------


def test_sharded_backend_bit_for_bit_sweep():
    """bits × radix × {col, row} × inner backend on an 8-way model axis:
    the sharded backend's output is bit-identical to the wrapped
    single-device backend (fp32 accumulate, integer-grid data)."""
    _run_sub("""
    from repro.dist import make_mesh
    from repro.engine import EnginePlan, pack_linear, partition_kind

    mesh = make_mesh((1, 8), ("data", "model"))
    n_cases = 0
    for bits in (2, 4, 8):
        for radix in (1, 2, 4):
            if bits % radix:
                continue
            for inner in ("reference", "bit_serial"):
                for kind, (k, n) in (("col", (128, 64)), ("row", (128, 20))):
                    w, x = grid_data(3, k, n, bits, seed=17 * bits + radix)
                    lin = pack_linear(w, bits)
                    assert partition_kind(lin, 8) == kind, (kind, bits)
                    ref = EnginePlan(backend=inner, bits=bits, radix=radix
                                     ).apply(lin, x, out_dtype=jnp.float32)
                    sh = EnginePlan(backend="sharded", bits=bits,
                                    radix=radix, mesh=mesh,
                                    inner_backend=inner
                                    ).apply(lin, x, out_dtype=jnp.float32)
                    np.testing.assert_array_equal(
                        np.asarray(ref), np.asarray(sh),
                        err_msg=f"{inner}/{kind} bits={bits} radix={radix}")
                    n_cases += 1
    assert n_cases == 32, n_cases  # 8 (bits, radix) pairs x 2 inner x 2
    print("bit-for-bit sweep OK:", n_cases, "cases")
    """)


def test_sharded_backend_pallas_inner_and_ranks():
    """The Pallas-interpret kernel as the wrapped backend, plus 1D and
    batched-3D activations through the sharded dispatch."""
    _run_sub("""
    from repro.dist import make_mesh
    from repro.engine import EnginePlan, pack_linear

    mesh = make_mesh((1, 8), ("data", "model"))
    w, x = grid_data(3, 128, 64, 8, seed=5)
    lin = pack_linear(w, 8)
    for xx in (x, x[0], jnp.stack([x, 2.0 * x])):   # 2D, 1D, batched 3D
        ref = EnginePlan(backend="pallas_interpret", bits=8).apply(
            lin, xx, out_dtype=jnp.float32)
        sh = EnginePlan(backend="sharded", bits=8, mesh=mesh,
                        inner_backend="pallas_interpret").apply(
            lin, xx, out_dtype=jnp.float32)
        assert sh.shape == ref.shape
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(sh))
    print("pallas inner + rank sweep OK")
    """)


def test_sharded_backend_compressed_psum():
    """psum_bits=8 row-parallel reduction: within the compressed-psum
    bound (n_dev * scale/2 per element) of the exact reduction."""
    _run_sub("""
    from repro.dist import make_mesh
    from repro.engine import EnginePlan, pack_linear

    mesh = make_mesh((1, 8), ("data", "model"))
    w, x = grid_data(4, 128, 20, 8, seed=9)
    lin = pack_linear(w, 8)
    exact = EnginePlan(backend="sharded", bits=8, mesh=mesh,
                       inner_backend="reference").apply(
        lin, x, out_dtype=jnp.float32)
    comp = EnginePlan(backend="sharded", bits=8, mesh=mesh,
                      inner_backend="reference", psum_bits=8).apply(
        lin, x, out_dtype=jnp.float32)
    # the compressed wire scale is absmax over the *partials* (pmax'd) /
    # qmax; reconstruct the partials exactly from the dequantized weight
    wq, xs = np.asarray(lin.dequantize(), np.float64), np.asarray(x)
    parts = [xs[:, i * 16:(i + 1) * 16] @ wq[i * 16:(i + 1) * 16]
             for i in range(8)]
    absmax = max(np.abs(p).max() for p in parts)
    bound = 8.0 * (absmax / 127.0) / 2.0   # n_dev roundings of scale/2
    err = float(jnp.max(jnp.abs(exact - comp)))
    assert err <= bound * 1.0001, (err, bound)
    print("compressed psum err", err, "<= bound", bound)
    """)


# ---------------------------------------------------------------------------
# mesh-native paged serving
# ---------------------------------------------------------------------------


def test_paged_serving_on_mesh_token_identical():
    """Paged greedy decode on a (data=4, model=2) mesh — lanes and pages
    over data, KV heads over model — is token-identical to the unsharded
    paged engine, including under preemption (page pool too small for all
    residents)."""
    _run_sub("""
    from conftest import reduced_f32
    from repro.config.base import EngineConfig, ServeConfig
    from repro.dist import make_mesh
    from repro.models import init_params
    from repro.serve import ServeEngine

    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    PROMPTS = [[1, 2, 3], [4], [5, 6], [7, 8, 9, 10]]

    def gen(mesh=None, n_pages=None, max_new=6):
        scfg = ServeConfig(max_new_tokens=max_new, engine=EngineConfig())
        eng = ServeEngine(cfg, params, scfg, n_slots=4, max_len=32,
                          mode="paged", page_size=4, n_pages=n_pages,
                          prefill_chunk=3, mesh=mesh)
        for p in PROMPTS:
            eng.submit(p)
        return eng, sorted(eng.run(), key=lambda r: r.rid)

    mesh = make_mesh((4, 2), ("data", "model"))
    _, ref = gen()
    eng, shard = gen(mesh=mesh)
    # the pool really is sharded: pages over data, heads over model
    kspec = eng.pages.k.sharding.spec
    assert "model" in str(kspec) and "data" in str(kspec), kspec
    for a, b in zip(ref, shard):
        assert a.output == b.output, (a.rid, a.output, b.output)
    print("mesh == unsharded:", [r.output for r in shard])

    # preemption: 12 pages (divisible by data=4) cannot hold 4 residents
    _, ref_p = gen(n_pages=12, max_new=16)
    e2, shard_p = gen(mesh=mesh, n_pages=12, max_new=16)
    assert e2.preemptions > 0
    for a, b in zip(ref_p, shard_p):
        assert a.output == b.output, (a.rid, a.output, b.output)
    print("preemption token-identity OK:", e2.preemptions, "preemptions")
    """)


def test_fused_attn_on_mesh_token_identical():
    """The fused paged-attention kernel shard_mapped over the (4, 2) mesh
    (KV heads over model, lanes over data) matches the gather backend on
    the same mesh — decode and in-kernel chunked prefill, under
    preemption, and through prefix-cache hits whose suffix-only prefill
    starts mid-page.  f32 pools are token-identical; int8 pools round
    probabilities at a different point on the two paths, so there the
    fused engine is fed the gather engine's tokens and every logit
    vector must agree within ``KV8_LOGIT_TOL``."""
    _run_sub("""
    from conftest import KV8_LOGIT_TOL, reduced_f32, serve_logged
    from repro.config.base import EngineConfig, ServeConfig
    from repro.dist import make_mesh
    from repro.models import init_params
    from repro.serve import ServeEngine

    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    PROMPTS = [[1, 2, 3], [4], [5, 6], [7, 8, 9, 10]]
    mesh = make_mesh((4, 2), ("data", "model"))

    def engine(abk, kv_bits=0, n_slots=4, n_pages=None, max_new=6,
               prefix_cache=False):
        eng_cfg = (EngineConfig(kv_bits=kv_bits, backend="reference")
                   if kv_bits else EngineConfig())
        scfg = ServeConfig(max_new_tokens=max_new, engine=eng_cfg)
        return ServeEngine(cfg, params, scfg, n_slots=n_slots, max_len=32,
                           mode="paged", page_size=4, n_pages=n_pages,
                           prefill_chunk=3, mesh=mesh, attn_backend=abk,
                           prefix_cache=prefix_cache)

    def gen(abk, prompts=PROMPTS, **kw):
        eng = engine(abk, **kw)
        for p in prompts:
            eng.submit(list(p))
        return eng, [r.output for r in sorted(eng.run(),
                                              key=lambda r: r.rid)]

    eng, ref = gen("gather")
    _, fused = gen("pallas_interpret")
    kspec = eng.pages.k.sharding.spec
    assert "model" in str(kspec), kspec  # pool really head-sharded
    assert ref == fused, (ref, fused)
    print("fused==gather on mesh, kv_bits 0")

    outs, ref_lg = serve_logged(engine("gather", kv_bits=8), PROMPTS)
    forced, fused_lg = serve_logged(engine("pallas_interpret", kv_bits=8),
                                    PROMPTS, forced=outs)
    assert forced == outs and sorted(ref_lg) == sorted(fused_lg)
    for rid in ref_lg:
        assert ref_lg[rid].shape == fused_lg[rid].shape == (6, cfg.vocab_size)
        np.testing.assert_allclose(fused_lg[rid], ref_lg[rid], rtol=0,
                                   atol=KV8_LOGIT_TOL)
    print("fused~gather logits on mesh, kv_bits 8")

    # preemption: 12 pages cannot hold 4 residents at max_new=16
    e_ref, ref_p = gen("gather", n_pages=12, max_new=16)
    e_fus, fused_p = gen("pallas_interpret", n_pages=12, max_new=16)
    assert e_ref.preemptions > 0 and e_fus.preemptions > 0
    assert ref_p == fused_p
    print("preemption OK:", e_fus.preemptions, "preemptions")

    # prefix-cache: serialized admission so the repeats hit; the matches
    # end mid-page, so fused suffix-only prefill starts at a non-aligned
    # pos0 inside the shard_mapped grid
    a = list(range(1, 13))
    pc_prompts = [a, list(range(1, 11)) + [99, 100], list(a)]
    e_ref, ref_c = gen("gather", prompts=pc_prompts, n_slots=1,
                       prefix_cache=True)
    e_fus, fused_c = gen("pallas_interpret", prompts=pc_prompts,
                         n_slots=1, prefix_cache=True)
    assert e_fus.prefix_stats()["hits"] >= 2, e_fus.prefix_stats()
    assert e_ref.prefix_stats() == e_fus.prefix_stats()
    assert ref_c == fused_c
    print("prefix-cache on mesh OK:", e_fus.prefix_stats()["hits"], "hits")
    """)


def test_paged_serving_sharded_weights_on_mesh():
    """Full mesh-native stack: int8 bit-planed weights through the
    ``sharded`` backend + the sharded page pool, vs the same quantized
    engine on one device.  Greedy tokens match (integer-exact weight
    GEMV partials keep the stream stable on this seed)."""
    _run_sub("""
    from conftest import reduced_f32
    from repro.config.base import EngineConfig, ServeConfig
    from repro.dist import make_mesh
    from repro.models import init_params
    from repro.serve import ServeEngine

    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    PROMPTS = [[1, 2, 3], [4], [5, 6], [7, 8, 9, 10]]

    def gen(mesh=None, engine=None):
        scfg = ServeConfig(max_new_tokens=6, engine=engine)
        eng = ServeEngine(cfg, params, scfg, n_slots=4, max_len=32,
                          mode="paged", page_size=4, prefill_chunk=3,
                          mesh=mesh)
        for p in PROMPTS:
            eng.submit(p)
        return eng, sorted(eng.run(), key=lambda r: r.rid)

    mesh = make_mesh((4, 2), ("data", "model"))
    _, ref = gen(engine=EngineConfig(weight_bits=8, backend="reference"))
    e, shard = gen(mesh=mesh, engine=EngineConfig(
        weight_bits=8, backend="reference", sharded=True))
    assert e.plan.backend == "sharded" and e.plan.mesh is mesh
    for a, b in zip(ref, shard):
        assert a.output == b.output, (a.rid, a.output, b.output)
    print("sharded-weights serving token-identical")
    """)
