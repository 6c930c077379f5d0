"""Attention paths: dense masked, chunked-flash (online softmax, scan over
KV blocks — O(S·block) memory, required for the 32k prefill cells), decode
with KV cache (bf16/f32 or int8 bit-planed), and decode/prefill reads
through a paged-KV block table (the continuous-batching serving layout).

All paths share GQA semantics: Hq query heads grouped over Hkv KV heads.
"""

from __future__ import annotations

from typing import Optional

import functools

import jax
import jax.numpy as jnp

from repro.dist.hints import with_hint

NEG_INF = -1e30
FLASH_THRESHOLD = 4096  # switch to chunked path at/above this many KV tokens
FLASH_BLOCK_Q = 512
FLASH_BLOCK_KV = 1024

# On TPU hardware flip this to route attend_flash through the fused Pallas
# kernel (kernels/flash_attention): scores and softmax stats stay in VMEM,
# collapsing attention HBM traffic to Q/K/V/O.  The CPU dry-run keeps the
# jnp path; the kernel wrapper picks interpret-vs-compiled itself from the
# engine backend registry (repro.engine.default_interpret), so flipping
# this flag is safe on any host.
PALLAS_FLASH = False


def _scoped(name):
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*a, **k):
            with jax.named_scope(name):
                return fn(*a, **k)
        return inner
    return wrap


def _group_query_heads(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """(B,S,Hq,D) -> (B,S,Hkv,G,D)."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def _mask(q_pos, kv_pos, window):
    """Causal (+ optional sliding window) mask: (…, Sq, Skv) boolean.

    ``window`` may be a python int or a traced scalar (per-layer flag under
    a scan); window <= 0 means full causal attention.
    """
    causal = kv_pos[..., None, :] <= q_pos[..., :, None]
    near = kv_pos[..., None, :] > (q_pos[..., :, None] - window)
    return jnp.logical_and(causal, jnp.where(window > 0, near, True))


@_scoped("attend_dense")
def attend_dense(
    q: jnp.ndarray,            # (B, Sq, Hq, D)
    k: jnp.ndarray,            # (B, Skv, Hkv, D)
    v: jnp.ndarray,
    q_pos: jnp.ndarray,        # (B, Sq)
    kv_pos: jnp.ndarray,       # (B, Skv)
    window: int = 0,
    kv_valid: Optional[jnp.ndarray] = None,  # (B, Skv) bool
    softcap: float = 0.0,
) -> jnp.ndarray:
    b, sq, hq, d = q.shape
    n_kv = k.shape[2]
    qg = _group_query_heads(q, n_kv)                       # (B,Sq,Hkv,G,D)
    scale = d ** -0.5
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    mask = _mask(q_pos, kv_pos, window)[:, None, None]     # (B,1,1,Sq,Skv)
    if kv_valid is not None:
        mask = jnp.logical_and(mask, kv_valid[:, None, None, None, :])
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, d).astype(q.dtype)


@_scoped("attend_dense_quant")
def attend_dense_quant(
    q: jnp.ndarray,            # (B, Sq, Hq, D)
    k: jnp.ndarray,            # (B, Skv, Hkv, D) int8
    v: jnp.ndarray,
    k_scale: jnp.ndarray,      # (B, Skv, Hkv)
    v_scale: jnp.ndarray,
    q_pos: jnp.ndarray,        # (B, Sq)
    kv_pos: jnp.ndarray,       # (B, Skv)
    window: int = 0,
    kv_valid: Optional[jnp.ndarray] = None,  # (B, Skv) bool
) -> jnp.ndarray:
    """Dense attention over an int8 KV view without dequantizing it.

    The chunked-prefill int8 path used to materialize the *entire*
    gathered KV view in fp32 (4× the cache bytes per chunk) just to call
    :func:`attend_dense`.  Here the scales fold into the probabilities
    exactly as :func:`attend_decode_quant` does on the decode path —
    ``scores_t = (q·k_t)·s_k[t]``, ``out = Σ_t (p_t·s_v[t])·v_t`` — so
    the contraction reads the int8 view directly (1 byte/element) and the
    fp32 copy never exists.
    """
    b, sq, hq, d = q.shape
    n_kv = k.shape[2]
    qg = _group_query_heads(q, n_kv).astype(jnp.bfloat16)  # (B,Sq,Hkv,G,D)
    scale = d ** -0.5
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32) * scale
    ks = k_scale.astype(jnp.float32).transpose(0, 2, 1)    # (B,Hkv,Skv)
    scores = scores * ks[:, :, None, None, :]
    mask = _mask(q_pos, kv_pos, window)[:, None, None]     # (B,1,1,Sq,Skv)
    if kv_valid is not None:
        mask = jnp.logical_and(mask, kv_valid[:, None, None, None, :])
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    vs = v_scale.astype(jnp.float32).transpose(0, 2, 1)
    pv = probs * vs[:, :, None, None, :]
    out = jnp.einsum("bhgqk,bkhd->bqhgd", pv.astype(jnp.bfloat16),
                     v.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, hq, d).astype(q.dtype)


@_scoped("attend_flash")
def attend_flash(
    q: jnp.ndarray,            # (B, S, Hq, D)
    k: jnp.ndarray,            # (B, S, Hkv, D)
    v: jnp.ndarray,
    positions: jnp.ndarray,    # (B, S)
    window: int = 0,
    block_q: int = FLASH_BLOCK_Q,
    block_kv: int = FLASH_BLOCK_KV,
) -> jnp.ndarray:
    """Chunked online-softmax causal attention (pure-jnp flash).

    Outer ``lax.scan`` over query blocks, inner ``lax.scan`` over KV blocks,
    running (max, sumexp, out) carry — peak live memory is
    O(B · Hq · block_q · block_kv) instead of O(S^2).  Fully-masked KV
    blocks are still *computed* (static schedule) but contribute zeros; the
    windowed-gather path below avoids that waste for local layers.
    """
    if PALLAS_FLASH and isinstance(window, int):
        from repro.kernels.flash_attention.ops import flash_attention

        return flash_attention(q, k, v, window=window)
    b, s, hq, d = q.shape
    n_kv = k.shape[2]
    g = hq // n_kv
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)
    blk = max(block_q, block_kv)
    if s % blk != 0:
        # pad to a block multiple; padded keys get position +inf (masked by
        # causality for every real query), padded query outputs are sliced.
        pad = blk - s % blk
        out = attend_flash(
            jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(positions, ((0, 0), (0, pad)),
                    constant_values=2**30),
            window, block_q, block_kv)
        return out[:, :s]
    nq, nkv = s // block_q, s // block_kv
    scale = d ** -0.5

    # keep storage dtype; accumulate in f32 inside each block step
    qb = q.reshape(b, nq, block_q, hq, d)
    qpb = positions.reshape(b, nq, block_q)
    kb = k.reshape(b, nkv, block_kv, n_kv, d)
    vb = v.reshape(b, nkv, block_kv, n_kv, d)
    kpb = positions.reshape(b, nkv, block_kv)

    def q_step(_, qi):
        q_blk, q_pos = qi                                  # (B,bq,H,D), (B,bq)
        qg = q_blk.reshape(b, block_q, n_kv, g, d)

        def kv_step(carry, ki):
            m, l, acc = carry
            k_blk, v_blk, kv_pos = ki
            sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_blk,
                            preferred_element_type=jnp.float32) * scale
            msk = _mask(q_pos, kv_pos, window)[:, None, None]
            sc = jnp.where(msk, sc, NEG_INF)
            blk_max = jnp.max(sc, axis=-1)                 # (B,Hkv,G,bq)
            new_m = jnp.maximum(m, blk_max)
            corr = jnp.exp(m - new_m)
            p = jnp.exp(sc - new_m[..., None])
            new_l = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk,
                            preferred_element_type=jnp.float32)
            new_acc = acc * corr[..., None] + pv
            return (new_m, new_l, new_acc), None

        m0 = jnp.full((b, n_kv, g, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, n_kv, g, block_q), jnp.float32)
        a0 = jnp.zeros((b, n_kv, g, block_q, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
             kpb.transpose(1, 0, 2)),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]       # (B,Hkv,G,bq,D)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, block_q, hq, d)
        return None, out

    _, outs = jax.lax.scan(
        q_step, None,
        (qb.transpose(1, 0, 2, 3, 4), qpb.transpose(1, 0, 2)),
    )
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, s, hq, d)
    return out.astype(q.dtype)


@_scoped("attend_local_gather")
def attend_local_gather(
    q: jnp.ndarray,            # (B, S, Hq, D)
    k: jnp.ndarray,            # (B, S, Hkv, D)
    v: jnp.ndarray,
    positions: jnp.ndarray,    # (B, S)
    window: int,
) -> jnp.ndarray:
    """Sliding-window attention without O(S^2) score blocks.

    Each query block of size W attends to the gathered [start-W, end) KV
    range (2W keys) — total FLOPs O(S · 2W · D) instead of O(S^2 · D).
    This is the beyond-baseline optimization used by the gemma3 hillclimb.
    """
    b, s, hq, d = q.shape
    n_kv = k.shape[2]
    g = hq // n_kv
    w = window
    assert s % w == 0, (s, w)
    nq = s // w
    scale = d ** -0.5

    # pad one window of KV history at the front
    kpad = jnp.pad(k, ((0, 0), (w, 0), (0, 0), (0, 0)))
    vpad = jnp.pad(v, ((0, 0), (w, 0), (0, 0), (0, 0)))
    ppad = jnp.pad(positions, ((0, 0), (w, 0)), constant_values=-1)

    qb = q.reshape(b, nq, w, hq, d).astype(jnp.float32)
    qpb = positions.reshape(b, nq, w)
    # window i covers padded range [i*w, i*w + 2w)
    kw = jnp.stack([jax.lax.dynamic_slice_in_dim(kpad, i * w, 2 * w, 1)
                    for i in range(nq)], 1).astype(jnp.float32)
    vw = jnp.stack([jax.lax.dynamic_slice_in_dim(vpad, i * w, 2 * w, 1)
                    for i in range(nq)], 1).astype(jnp.float32)
    pw = jnp.stack([jax.lax.dynamic_slice_in_dim(ppad, i * w, 2 * w, 1)
                    for i in range(nq)], 1)

    qg = qb.reshape(b, nq, w, n_kv, g, d)
    sc = jnp.einsum("bnqhgd,bnkhd->bnhgqk", qg, kw) * scale
    msk = _mask(qpb, pw, w)[:, :, None, None]
    msk = jnp.logical_and(msk, (pw >= 0)[:, :, None, None, None, :])
    sc = jnp.where(msk, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bnhgqk,bnkhd->bnqhgd", p, vw)
    return out.reshape(b, s, hq, d).astype(q.dtype)


@_scoped("attend_decode")
def attend_decode(
    q: jnp.ndarray,            # (B, 1, Hq, D)
    k_cache: jnp.ndarray,      # (B, T, Hkv, D)
    v_cache: jnp.ndarray,
    cur_pos: jnp.ndarray,      # (B,) current token position (0-based)
    window: int = 0,
) -> jnp.ndarray:
    """Single-token decode over a (possibly sequence-sharded) KV cache."""
    b, t, n_kv, d = k_cache.shape
    hq = q.shape[2]
    g = hq // n_kv
    scale = d ** -0.5
    # NOTE: the cache stays in its storage dtype — einsum accumulates in
    # f32 via preferred_element_type.  Upcasting the cache would force XLA
    # to materialize a full-cache f32 copy inside the per-layer loop (a 60x
    # HBM-traffic bug caught by the dry-run profiler).
    qg = q.reshape(b, n_kv, g, d).astype(k_cache.dtype)
    sc = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                    preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(t)[None, :]                        # (1,T)
    valid = kv_pos <= cur_pos[:, None]
    near = kv_pos > cur_pos[:, None] - window
    valid = jnp.logical_and(valid, jnp.where(window > 0, near, True))
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, d).astype(q.dtype)


@_scoped("attend_decode_quant")
def attend_decode_quant(
    q: jnp.ndarray,            # (B, 1, Hq, D)
    k_cache: jnp.ndarray,      # (B, T, Hkv, D) int8
    v_cache: jnp.ndarray,
    k_scale: jnp.ndarray,      # (B, T, Hkv)
    v_scale: jnp.ndarray,
    cur_pos: jnp.ndarray,      # (B,)
    window: int = 0,
) -> jnp.ndarray:
    """Decode attention over an int8 cache: scores_t = (q·k_t)·s_k[t];
    output = Σ_t (p_t·s_v[t])·v_t — scales fold into the probabilities so
    the contraction stays int8 (1 byte/element of cache traffic)."""
    b, t, n_kv, dh = k_cache.shape
    hq = q.shape[2]
    g = hq // n_kv
    scale = dh ** -0.5
    qg = q.reshape(b, n_kv, g, dh).astype(jnp.bfloat16)
    sc = jnp.einsum("bhgd,bkhd->bhgk", qg,
                    k_cache.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32) * scale
    sc = sc * k_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
    kv_pos = jnp.arange(t)[None, :]
    valid = kv_pos <= cur_pos[:, None]
    near = kv_pos > cur_pos[:, None] - window
    valid = jnp.logical_and(valid, jnp.where(window > 0, near, True))
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    pv = p * v_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
    out = jnp.einsum("bhgk,bkhd->bhgd", pv.astype(jnp.bfloat16),
                     v_cache.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged-KV reads: K/V live in a shared head-major (Hkv, P, page, D) page
# pool and are addressed per request through a (B, n_blocks) block table.
# ---------------------------------------------------------------------------


def gather_kv_pages(pages: jnp.ndarray,
                    block_tables: jnp.ndarray) -> jnp.ndarray:
    """Materialize each lane's logical KV view from the page pool.

    ``pages``: head-major ``(Hkv, P, page, ...)`` physical pool (one layer
    of K, V or a scale pool); ``block_tables``: ``(B, n_blocks)`` int32
    physical page ids in logical order.  Returns ``(B, n_blocks * page,
    Hkv, ...)`` — logical position ``t`` of lane ``b``, head ``h`` lives at
    ``pages[h, block_tables[b, t // page], t % page]``.
    """
    g = jnp.take(pages, block_tables, axis=1)      # (Hkv, B, nblk, page, ...)
    hkv, b, nblk, page = g.shape[:4]
    out = jnp.moveaxis(g.reshape((hkv, b, nblk * page) + g.shape[4:]), 0, 2)
    # mesh-native serving: lanes over the data axes, KV heads over
    # ``model`` — axis 2 is Hkv for K/V pools (B, T, Hkv, Dh) *and* for
    # scale pools (B, T, Hkv), so one hint covers both.  No-op off-mesh.
    return with_hint(out, ("pod", "data"), None, "model")


@_scoped("attend_paged_decode")
def attend_paged_decode(
    q: jnp.ndarray,            # (B, 1, Hq, D)
    k_pages: jnp.ndarray,      # (Hkv, P, page, D) — one layer's pool
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, n_blocks) int32
    cur_pos: jnp.ndarray,      # (B,) position of the newest token
    window: int = 0,
    k_scale: Optional[jnp.ndarray] = None,  # (Hkv, P, page) int8 pools only
    v_scale: Optional[jnp.ndarray] = None,
    attn_backend: str = "gather",
    mesh=None,
    model_axis: str = "model",
) -> jnp.ndarray:
    """Single-token decode reading K/V through the block table.

    ``attn_backend`` picks the read path (resolved once into the plan —
    ``EnginePlan.attn_backend`` — and threaded down, never decided here):

    * ``gather`` — the reference: materialize each lane's logical KV view
      from the pool, then attend.  The gathered view is exactly the dense
      cache the fixed-slot engine holds (unwritten logical positions are
      masked by ``cur_pos``), so this path is token-identical to
      :func:`attend_decode` — pages only change *where* the bytes live,
      not the math.
    * ``pallas_interpret`` / ``pallas_tpu`` — the fused in-place kernel
      (``repro.kernels.paged_attention``): the block table drives the K/V
      BlockSpec index maps, pages are read from the pool exactly once and
      the gathered copy never exists; token-identity against ``gather``
      is pinned by ``tests/test_paged_attention.py``.

    ``mesh`` (fused backends only): shard_map the kernel over
    ``model_axis`` — each shard's kernel invocation runs on the
    contiguous KV-head slice its pool shard already holds
    (``repro.engine.sharded.sharded_paged_attention``).  The gather path
    composes with a mesh through its sharding hints instead and ignores
    these arguments.
    """
    if attn_backend in ("pallas_interpret", "pallas_tpu"):
        from repro.kernels.paged_attention.ops import paged_attention

        return paged_attention(q, k_pages, v_pages, block_tables, cur_pos,
                               window, k_scale, v_scale,
                               attn_backend=attn_backend,
                               mesh=mesh, model_axis=model_axis)
    if attn_backend != "gather":
        raise ValueError(f"unknown attention backend {attn_backend!r}")
    kg = gather_kv_pages(k_pages, block_tables)
    vg = gather_kv_pages(v_pages, block_tables)
    if k_scale is not None:
        ksg = gather_kv_pages(k_scale, block_tables)
        vsg = gather_kv_pages(v_scale, block_tables)
        return attend_decode_quant(q, kg, vg, ksg, vsg, cur_pos, window)
    return attend_decode(q, kg, vg, cur_pos, window)


@_scoped("attend_paged_prefill")
def attend_paged_prefill(
    q: jnp.ndarray,            # (B, C, Hq, D) — one prefill chunk
    k_pages: jnp.ndarray,      # (Hkv, P, page, D) — one layer's pool
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, n_blocks) int32
    positions: jnp.ndarray,    # (B, C) logical positions of the chunk
    pos0: jnp.ndarray,         # (B,) tokens already resident per lane
    seq_lens: jnp.ndarray,     # (B,) total valid after this chunk
    window: int = 0,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    attn_backend: str = "gather",
    mesh=None,
    model_axis: str = "model",
) -> jnp.ndarray:
    """One prefill chunk's attention reading K/V through the block table.

    The chunk's K/V must already be scattered into the pool; lane ``b``'s
    queries cover logical positions ``[pos0[b], pos0[b]+C)`` (suffix-only
    prefill after a prefix-cache hit arrives with ``pos0`` mid-page) and
    attend the lane's full resident prefix plus this chunk, causally,
    clipped to ``limit = min(seq_lens, pos0 + C)``.

    * ``gather`` — materialize the logical view, then :func:`attend_dense`
      / :func:`attend_dense_quant` (the reference; carries sharding hints).
    * ``pallas_interpret`` / ``pallas_tpu`` — the fused prefill grid
      (``kernels.paged_attention.paged_prefill_pallas``): per-lane
      ``pos0`` / ``seq_lens`` travel as scalar-prefetch operands and the
      gathered ``(B, T, Hkv, D)`` view never exists.  With a ``mesh`` the
      kernel shard_maps over ``model_axis`` like the decode path.
    """
    if attn_backend in ("pallas_interpret", "pallas_tpu"):
        from repro.kernels.paged_attention.ops import paged_prefill_attention

        return paged_prefill_attention(
            q, k_pages, v_pages, block_tables, pos0, seq_lens, window,
            k_scale, v_scale, attn_backend=attn_backend,
            mesh=mesh, model_axis=model_axis)
    if attn_backend != "gather":
        raise ValueError(f"unknown attention backend {attn_backend!r}")
    b, c = q.shape[:2]
    t_total = block_tables.shape[1] * k_pages.shape[2]
    kv_pos = jnp.broadcast_to(
        jnp.arange(t_total, dtype=jnp.int32)[None, :], (b, t_total))
    limit = jnp.minimum(seq_lens, pos0 + c)
    kv_valid = kv_pos < limit[:, None]
    kg = gather_kv_pages(k_pages, block_tables)
    vg = gather_kv_pages(v_pages, block_tables)
    if k_scale is not None:
        ksg = gather_kv_pages(k_scale, block_tables)
        vsg = gather_kv_pages(v_scale, block_tables)
        return attend_dense_quant(q, kg, vg, ksg, vsg, positions, kv_pos,
                                  window, kv_valid=kv_valid)
    return attend_dense(q, kg, vg, positions, kv_pos, window,
                        kv_valid=kv_valid)
