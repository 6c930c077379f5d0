"""Fused paged-attention kernel: token-identity against the ``gather``
reference backend.

The kernel (``repro.kernels.paged_attention``) reads K/V pages in place
through the block table; these tests pin that the read path is a pure
relocation of bytes — page size × GQA group × sliding window × kv_bits
sweeps, a ragged last block, block tables reshuffled as preemption
free/re-alloc would leave them, and end-to-end greedy serving (including
under real preemption, reusing the ``test_serve_paged`` geometry).  The
in-kernel chunked-prefill grid gets the same treatment: ragged last
pages, mid-page ``pos0`` (a prefix-cache match ending inside a page),
int8 pools and sliding-window layers, each pinned against the gather
prefill path that materializes the KV view."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import EngineConfig, ServeConfig
from repro.engine import ATTN_BACKENDS, EnginePlan, resolve_attn_backend
from repro.kernels.paged_attention.ops import (
    decode_attn_bytes,
    prefill_attn_bytes,
    synthetic_prefill_case,
)
from repro.kernels.paged_attention.ref import (
    paged_attention_ref,
    paged_prefill_ref,
)
from repro.models import init_params
from repro.models.attention import attend_paged_decode, attend_paged_prefill
from repro.serve import ServeEngine

from conftest import KV8_LOGIT_TOL, reduced_f32, serve_logged

PROMPTS = [[1, 2, 3], [4], [5, 6], [7, 8, 9, 10]]


def _pool(rng, n_pages, page, hkv, dh, kv_bits):
    """One layer's head-major pool: ``(Hkv, P, page, Dh)`` K/V and
    ``(Hkv, P, page)`` scales."""
    if kv_bits:
        kp = rng.integers(-127, 128, (hkv, n_pages, page, dh)).astype(np.int8)
        vp = rng.integers(-127, 128, (hkv, n_pages, page, dh)).astype(np.int8)
        ks = rng.uniform(0.004, 0.02, (hkv, n_pages, page))
        vs = rng.uniform(0.004, 0.02, (hkv, n_pages, page))
        return (jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(ks, jnp.bfloat16), jnp.asarray(vs, jnp.bfloat16))
    kp = rng.standard_normal((hkv, n_pages, page, dh)).astype(np.float32)
    vp = rng.standard_normal((hkv, n_pages, page, dh)).astype(np.float32)
    return jnp.asarray(kp), jnp.asarray(vp), None, None


def _both(q, kp, vp, bt, pos, win, ks, vs):
    a = attend_paged_decode(q, kp, vp, bt, pos, win, k_scale=ks, v_scale=vs,
                            attn_backend="gather")
    b = attend_paged_decode(q, kp, vp, bt, pos, win, k_scale=ks, v_scale=vs,
                            attn_backend="pallas_interpret")
    return np.asarray(a), np.asarray(b)


# ------------------------------------------------------------- the sweep
@pytest.mark.parametrize(
    "page,group,window,kv_bits",
    [(p, g, w, kb)
     for p, g in itertools.product((2, 4), (1, 3))
     for w, kb in (((0, 0)), ((5, 0)), ((0, 8)), ((5, 8)))],
)
def test_fused_matches_gather(page, group, window, kv_bits):
    """Fused kernel output == gather output across page size × GQA group
    × sliding window × kv_bits, at ragged positions (last block partly
    unwritten) and distinct per-lane contexts."""
    rng = np.random.default_rng(7)
    b, hkv, dh, nblk = 3, 2, 8, 4
    hq = hkv * group
    n_pages = b * nblk + 1
    kp, vp, ks, vs = _pool(rng, n_pages, page, hkv, dh, kv_bits)
    bt = jnp.asarray(
        1 + rng.permutation(b * nblk).reshape(b, nblk), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, 1, hq, dh)), jnp.float32)
    # ragged everywhere: no lane sits on a page boundary, lane 2 has a
    # nearly-empty last block
    pos = jnp.asarray([page * nblk - 2, page + 1, 0], jnp.int32)
    a, f = _both(q, kp, vp, bt, pos, window, ks, vs)
    tol = 1e-2 if kv_bits else 1e-5
    np.testing.assert_allclose(a, f, rtol=tol, atol=tol)


def test_fused_close_on_bf16_pools():
    """bf16 pools (the default model dtype): the kernel mirrors the gather
    path's storage-dtype casts (q → pool dtype, p → V dtype), but online
    softmax normalizes *after* the bf16 rounding of p where the gather
    path normalizes before — agreement is within a bf16 ulp, not
    bitwise.  Exact token identity is pinned on f32 and int8 pools."""
    rng = np.random.default_rng(5)
    b, hkv, g, dh, page, nblk = 2, 2, 2, 8, 4, 3
    kp, vp, _, _ = _pool(rng, b * nblk + 1, page, hkv, dh, 0)
    kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    bt = jnp.asarray(1 + rng.permutation(b * nblk).reshape(b, nblk),
                     jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, 1, hkv * g, dh)), jnp.bfloat16)
    pos = jnp.asarray([9, 4], jnp.int32)
    a, f = _both(q, kp, vp, bt, pos, 0, None, None)
    np.testing.assert_allclose(a.astype(np.float32), f.astype(np.float32),
                               rtol=2e-2, atol=2e-2)


def test_fused_matches_standalone_ref():
    """The kernel package's own gather reference (no repro.models import)
    agrees too — kernel tests and benches can diff against it directly."""
    rng = np.random.default_rng(3)
    b, hkv, g, dh, page, nblk = 2, 2, 2, 8, 4, 3
    kp, vp, _, _ = _pool(rng, b * nblk + 1, page, hkv, dh, 0)
    bt = jnp.asarray(1 + rng.permutation(b * nblk).reshape(b, nblk),
                     jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, 1, hkv * g, dh)), jnp.float32)
    pos = jnp.asarray([9, 4], jnp.int32)
    ref = paged_attention_ref(q, kp, vp, bt, pos, 0)
    _, fused = _both(q, kp, vp, bt, pos, 0, None, None)
    np.testing.assert_allclose(np.asarray(ref), fused, rtol=1e-5, atol=1e-5)


def test_fused_invariant_under_page_reshuffle():
    """Preemption re-allocs hand a resumed request *different* physical
    pages; the same logical content through a permuted block table must
    produce bit-identical attention output."""
    rng = np.random.default_rng(11)
    b, hkv, g, dh, page, nblk = 2, 2, 2, 8, 4, 3
    n_pages = b * nblk + 1
    kp, vp, _, _ = _pool(rng, n_pages, page, hkv, dh, 0)
    bt = jnp.asarray(1 + np.arange(b * nblk).reshape(b, nblk), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, 1, hkv * g, dh)), jnp.float32)
    pos = jnp.asarray([10, 7], jnp.int32)

    perm = np.concatenate([[0], 1 + rng.permutation(n_pages - 1)])
    inv = np.argsort(perm)
    kp2 = kp[:, jnp.asarray(perm)]         # physical page p moves to inv[p]
    vp2 = vp[:, jnp.asarray(perm)]
    bt2 = jnp.asarray(inv[np.asarray(bt)], jnp.int32)

    _, f1 = _both(q, kp, vp, bt, pos, 0, None, None)
    _, f2 = _both(q, kp2, vp2, bt2, pos, 0, None, None)
    np.testing.assert_array_equal(f1, f2)


# ------------------------------------------------- in-kernel prefill grid
def _both_prefill(case, window=0):
    """(gather, fused) outputs of ``attend_paged_prefill`` on one case."""
    b, c = case["q"].shape[:2]
    positions = case["pos0"][:, None] + jnp.arange(c, dtype=jnp.int32)[None]
    args = (case["q"], case["k_pages"], case["v_pages"],
            case["block_tables"], positions, case["pos0"], case["seq_lens"],
            window, case["k_scale"], case["v_scale"])
    a = attend_paged_prefill(*args, attn_backend="gather")
    f = attend_paged_prefill(*args, attn_backend="pallas_interpret")
    return np.asarray(a), np.asarray(f)


@pytest.mark.parametrize("window,kv_bits",
                         [(0, 0), (6, 0), (0, 8), (6, 8)])
def test_prefill_fused_matches_gather(window, kv_bits):
    """The prefill grid == the gather prefill path across sliding window ×
    kv_bits, on the standard synthetic case: every lane's ``pos0`` lands
    mid-page (a prefix-cache match offset, not page-aligned) and the last
    lane's chunk is ragged (``seq_lens < pos0 + chunk``)."""
    rng = np.random.default_rng(17)
    case = synthetic_prefill_case(rng, batch=3, nblk=5, page=4, hkv=2,
                                  group=2, dh=16, chunk=6, kv_bits=kv_bits)
    a, f = _both_prefill(case, window)
    tol = 1e-2 if kv_bits else 1e-5
    np.testing.assert_allclose(a, f, rtol=tol, atol=tol)


def test_prefill_fused_ragged_last_page():
    """A chunk whose final KV page is mostly unwritten: the in-kernel
    ``kv_pos < limit`` mask must drop exactly the unwritten tail — one
    valid token on the last page, the rest garbage the gather path never
    materializes."""
    rng = np.random.default_rng(23)
    page, chunk = 4, 9            # pos0=0 → last page holds 1 of 4 slots
    case = synthetic_prefill_case(rng, batch=1, nblk=4, page=page, hkv=2,
                                  group=1, dh=8, chunk=chunk, kv_bits=0)
    case["pos0"] = jnp.zeros_like(case["pos0"])
    case["seq_lens"] = jnp.full_like(case["seq_lens"], chunk)
    a, f = _both_prefill(case)
    np.testing.assert_allclose(a, f, rtol=1e-5, atol=1e-5)


def test_prefill_fused_midpage_pos0():
    """Suffix-only prefill after a prefix-cache hit that ends *inside* a
    page: ``pos0`` is not page-aligned, so the first query row attends a
    partially-filled page and the causal mask starts mid-page."""
    rng = np.random.default_rng(29)
    page = 4
    case = synthetic_prefill_case(rng, batch=2, nblk=5, page=page, hkv=2,
                                  group=2, dh=8, chunk=5, kv_bits=0)
    pos0 = jnp.asarray([page + 2, 2 * page + 3], jnp.int32)  # both mid-page
    case["pos0"] = pos0
    case["seq_lens"] = pos0 + 5
    a, f = _both_prefill(case)
    np.testing.assert_allclose(a, f, rtol=1e-5, atol=1e-5)


def test_prefill_fused_matches_standalone_ref():
    """The kernel package's own prefill gather reference (no repro.models
    import) agrees — benches can diff against it directly."""
    rng = np.random.default_rng(31)
    case = synthetic_prefill_case(rng, batch=2, nblk=4, page=4, hkv=2,
                                  group=2, dh=8, chunk=6, kv_bits=0)
    ref = paged_prefill_ref(case["q"], case["k_pages"], case["v_pages"],
                            case["block_tables"], case["pos0"],
                            case["seq_lens"], 0, None, None)
    _, f = _both_prefill(case)
    b, c = case["q"].shape[:2]
    valid = np.asarray(case["seq_lens"] - case["pos0"])  # per-lane real rows
    for lane in range(b):
        np.testing.assert_allclose(np.asarray(ref)[lane, :valid[lane]],
                                   f[lane, :valid[lane]],
                                   rtol=1e-5, atol=1e-5)


# --------------------------------------------------- end-to-end serving
def _serve(cfg, params, abk, *, engine=None, max_new=5, n_slots=2,
           max_len=32, **kw):
    scfg = ServeConfig(max_new_tokens=max_new, engine=engine or EngineConfig())
    eng = ServeEngine(cfg, params, scfg, n_slots=n_slots, max_len=max_len,
                      mode="paged", attn_backend=abk, **kw)
    for p in PROMPTS:
        eng.submit(p)
    return eng, [r.output for r in sorted(eng.run(), key=lambda r: r.rid)]


@pytest.mark.parametrize("kv_bits", [0, 8])
def test_serve_token_identity(rng, kv_bits):
    """Serving through the fused kernel matches the gather backend —
    kv_bits ∈ {0, 8} through one dispatch.

    f32 pools (kv 0): the two read paths form the same f32 products, so
    greedy tokens are identical.  int8 pools (kv 8): both contract in
    bf16, but the kernel rounds the *unnormalized* probabilities where
    gather rounds normalized ones, so logits differ by bf16 rounding and
    a near-tie can flip a greedy token.  There the fused engine is fed
    the gather engine's tokens and every logit vector (prefill, then each
    decode step) must agree within ``KV8_LOGIT_TOL``."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    engine = (EngineConfig(kv_bits=kv_bits, backend="reference")
              if kv_bits else None)
    if not kv_bits:
        _, ref = _serve(cfg, params, "gather", engine=engine,
                        page_size=4, prefill_chunk=3)
        _, fused = _serve(cfg, params, "pallas_interpret", engine=engine,
                          page_size=4, prefill_chunk=3)
        assert ref == fused
        return

    def engine_for(abk):
        scfg = ServeConfig(max_new_tokens=5, engine=engine)
        return ServeEngine(cfg, params, scfg, n_slots=2, max_len=32,
                           mode="paged", attn_backend=abk, page_size=4,
                           prefill_chunk=3)

    outs, ref = serve_logged(engine_for("gather"), PROMPTS)
    forced, fused = serve_logged(engine_for("pallas_interpret"), PROMPTS,
                                 forced=outs)
    assert forced == outs and sorted(ref) == sorted(fused)
    for rid in ref:
        assert ref[rid].shape == fused[rid].shape == (5, cfg.vocab_size)
        np.testing.assert_allclose(fused[rid], ref[rid], rtol=0,
                                   atol=KV8_LOGIT_TOL)


def test_serve_token_identity_sliding_window(rng):
    """gemma3-family local/global stack: the traced per-layer window rides
    into the kernel as a runtime scalar under the layer scan."""
    cfg = reduced_f32("gemma3-27b")
    params = init_params(cfg, rng)
    _, ref = _serve(cfg, params, "gather", page_size=4, prefill_chunk=3)
    _, fused = _serve(cfg, params, "pallas_interpret", page_size=4,
                      prefill_chunk=3)
    assert ref == fused


@pytest.mark.parametrize("kv_bits", [0, 8])
def test_serve_token_identity_with_prefix_cache(rng, kv_bits):
    """Prefix-cache hits feed the in-kernel prefill grid a mid-page
    ``pos0`` (the B prompt's match ends 2 tokens into a page): cache-hit
    suffix-only prefill through the fused kernel matches the gather
    backend token for token, and the hit path really ran."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    a = list(range(1, 13))
    prompts = [a, list(range(1, 11)) + [99, 100], list(a), [71, 72, 73]]
    engine = (EngineConfig(kv_bits=kv_bits, backend="reference")
              if kv_bits else None)

    def gen(abk):
        scfg = ServeConfig(max_new_tokens=5, engine=engine or EngineConfig())
        # n_slots=1 serializes admission so B and C find A's pages
        # committed (their matches end mid-page: 10 and 11 tokens)
        eng = ServeEngine(cfg, params, scfg, n_slots=1, max_len=32,
                          mode="paged", attn_backend=abk, page_size=4,
                          prefill_chunk=3, prefix_cache=True)
        for p in prompts:
            eng.submit(list(p))
        return eng, [r.output for r in sorted(eng.run(),
                                              key=lambda r: r.rid)]

    ref_eng, ref = gen("gather")
    fused_eng, fused = gen("pallas_interpret")
    assert fused_eng.prefix_stats()["hits"] >= 2
    assert ref_eng.prefix_stats() == fused_eng.prefix_stats()
    assert ref == fused


def test_serve_token_identity_under_preemption(rng):
    """The test_serve_paged preemption geometry (pool too small for all
    residents), decoded through the fused kernel: recompute-resume with
    reshuffled block tables keeps greedy tokens exact."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    ref_eng, ref = _serve(cfg, params, "gather", max_new=16, n_slots=3,
                          max_len=48, page_size=4, n_pages=14,
                          prefill_chunk=4)
    fused_eng, fused = _serve(cfg, params, "pallas_interpret", max_new=16,
                              n_slots=3, max_len=48, page_size=4, n_pages=14,
                              prefill_chunk=4)
    assert ref_eng.preemptions > 0 and fused_eng.preemptions > 0
    assert ref == fused


# ------------------------------------------------------- plan threading
def test_plan_resolves_attn_backend():
    plan = EnginePlan(backend="reference", bits=8)
    assert plan.attn_backend in ("gather", "pallas_tpu")  # never "auto"
    if jax.default_backend() != "tpu":
        assert plan.attn_backend == "gather"
    pinned = EnginePlan(backend="reference", bits=8,
                        attn_backend="pallas_interpret")
    assert pinned.attn_backend == "pallas_interpret"
    with pytest.raises(KeyError):
        EnginePlan(backend="reference", bits=8, attn_backend="nope")
    assert resolve_attn_backend("gather") == "gather"
    assert resolve_attn_backend(None) in ATTN_BACKENDS


def test_auto_no_longer_downgrades_on_mesh():
    """'auto' resolves identically with and without a mesh: the fused
    kernel shard_maps over the pool's model axis now, so a mesh-carrying
    TPU plan runs fused by default (the old downgrade of auto-on-mesh to
    gather is gone).  On this host that means both resolve to the same
    host default; an explicit pallas name is still honored anywhere."""
    from repro.dist import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    assert (resolve_attn_backend("auto", mesh=mesh)
            == resolve_attn_backend("auto"))
    plan = EnginePlan(backend="reference", bits=8, mesh=mesh)
    flat = EnginePlan(backend="reference", bits=8)
    assert plan.attn_backend == flat.attn_backend  # mesh changes nothing
    pinned = EnginePlan(backend="reference", bits=8, mesh=mesh,
                        attn_backend="pallas_interpret")
    assert pinned.attn_backend == "pallas_interpret"


def test_serve_engine_honors_config_attn_backend(rng):
    """EngineConfig.attn_backend reaches the engine even when the engine
    is otherwise disabled (plan resolves to None)."""
    cfg = reduced_f32("qwen2.5-3b")
    params = init_params(cfg, rng)
    scfg = ServeConfig(engine=EngineConfig(attn_backend="pallas_interpret"))
    eng = ServeEngine(cfg, params, scfg, n_slots=1, max_len=16, mode="paged")
    assert eng.plan is None
    assert eng.attn_backend == "pallas_interpret"
    # explicit kwarg wins over the config
    eng2 = ServeEngine(cfg, params, scfg, n_slots=1, max_len=16,
                       mode="paged", attn_backend="gather")
    assert eng2.attn_backend == "gather"


# ------------------------------------------------------ bytes-moved model
def test_bytes_model_fused_below_gather():
    """The modeled read-path traffic of the fused kernel is strictly below
    gather at every context length >= one page, both precisions.  (A
    self-consistency check of the analytic model — it guards edits to
    ``decode_attn_bytes``; the kernel's real traffic is a TPU item.)"""
    for kv_bits in (0, 8):
        for context in (4, 16, 64, 512, 4096):
            kw = dict(batch=4, context=context, n_kv_heads=4, head_dim=64,
                      n_q_heads=8, page_size=4, kv_bits=kv_bits)
            gather = decode_attn_bytes("gather", **kw)
            fused = decode_attn_bytes("pallas_interpret", **kw)
            assert fused < gather, (kv_bits, context, fused, gather)
            # the win is the dropped view write + re-read: ~3x on the
            # KV term, diluted only by the shared Q/O traffic
            assert gather - fused > gather / 3


def test_prefill_bytes_model_fused_below_gather():
    """Same self-consistency guard for the chunked-prefill traffic model:
    in-kernel prefill never materializes the gathered (B, T, Hkv, Dh)
    view, so its modeled bytes sit below gather at every context — and
    once the context dwarfs the chunk (the KV view term dominating the
    shared Q/O traffic) the dropped write + re-read is most of the
    total, same ~3x-on-the-view win as decode."""
    for kv_bits in (0, 8):
        for context in (16, 64, 512, 4096):
            kw = dict(batch=4, chunk=16, context=context, n_kv_heads=4,
                      head_dim=64, n_q_heads=8, page_size=4,
                      kv_bits=kv_bits)
            gather = prefill_attn_bytes("gather", **kw)
            fused = prefill_attn_bytes("pallas_interpret", **kw)
            assert fused < gather, (kv_bits, context, fused, gather)
            if context >= 32 * kw["chunk"]:  # view-dominated regime
                assert gather - fused > gather / 3
