"""Public wrappers for the fused paged-attention kernels (decode and
chunked prefill).

Accept the model layouts (decode ``q`` as ``(B, 1, Hq, Dh)``, prefill
``q`` as ``(B, C, Hq, Dh)``, head-major pools as ``(Hkv, P, page, Dh)``,
scale pools as ``(Hkv, P, page)``) plus an
*attention backend name* — models/, serve/ and benchmarks/ never decide
interpret booleans themselves (the EnginePlan hygiene rule); the name →
interpret mapping lives here, next to the kernel.  A ``mesh`` routes the
call through ``repro.engine.sharded``'s shard_map wrapper (KV heads over
the plan's model axis — the pool is already placed that way).

:func:`decode_attn_bytes` / :func:`prefill_attn_bytes` — the bytes-moved
models the attention benchmarks and the micro-bench derived columns
share — are re-exported from :mod:`repro.obs.costs`, the one analytic
cost model the serve-path ledger, the roofline summary and every
benchmark now price against: the fused kernels read each pool page
exactly once per (lane, kv head) while the gather backend pays
pool-read + view-write + view-read for the same logical view.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import (
    paged_attention_pallas,
    paged_prefill_pallas,
)
from repro.obs.costs import (  # noqa: F401  (re-export: THE bytes model)
    decode_attn_bytes,
    prefill_attn_bytes,
)

PREFILL_BLOCK_Q = 128  # cap on query rows per prefill grid step


def paged_attention(
    q: jnp.ndarray,            # (B, 1, Hq, Dh) — model decode layout
    k_pages: jnp.ndarray,      # (Hkv, P, page, Dh)
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, n_blocks) int32
    cur_pos: jnp.ndarray,      # (B,)
    window=0,                  # python int or traced scalar; <= 0 = full
    k_scale: Optional[jnp.ndarray] = None,  # (Hkv, P, page) int8 pools only
    v_scale: Optional[jnp.ndarray] = None,
    *,
    attn_backend: str,
    mesh=None,
    model_axis: str = "model",
) -> jnp.ndarray:
    """Fused in-place paged decode attention; returns ``(B, 1, Hq, Dh)``.

    ``attn_backend`` must be one of the kernel-backed names
    (``pallas_interpret`` / ``pallas_tpu``); the ``gather`` reference path
    lives in ``repro.models.attention.attend_paged_decode``.  ``mesh``
    shard_maps the kernel over ``model_axis`` (per-shard head slices; see
    ``repro.engine.sharded.sharded_paged_attention``).
    """
    if attn_backend not in ("pallas_interpret", "pallas_tpu"):
        raise ValueError(
            f"paged_attention runs the fused kernel only "
            f"(pallas_interpret/pallas_tpu); got {attn_backend!r}")
    b, _, hq, d = q.shape
    hkv = k_pages.shape[0]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    win = jnp.asarray(window, jnp.int32).reshape(1)
    interpret = attn_backend == "pallas_interpret"
    if mesh is not None:
        from repro.engine.sharded import sharded_paged_attention

        out = sharded_paged_attention(
            mesh, model_axis, qg, k_pages, v_pages, block_tables,
            cur_pos, win, k_scale, v_scale, interpret=interpret)
    else:
        out = paged_attention_pallas(
            qg, k_pages, v_pages, block_tables, cur_pos, win,
            k_scale, v_scale, interpret=interpret)
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def paged_prefill_attention(
    q: jnp.ndarray,            # (B, C, Hq, Dh) — model prefill layout
    k_pages: jnp.ndarray,      # (Hkv, P, page, Dh)
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, n_blocks) int32
    pos0: jnp.ndarray,         # (B,) tokens already resident per lane
    seq_lens: jnp.ndarray,     # (B,) total valid after this chunk
    window=0,                  # python int or traced scalar; <= 0 = full
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    *,
    attn_backend: str,
    mesh=None,
    model_axis: str = "model",
) -> jnp.ndarray:
    """Fused in-place paged chunked-prefill attention; ``(B, C, Hq, Dh)``.

    The chunk's K/V must already be scattered into the pool (the kernel
    only reads).  Lane ``b``'s queries cover logical positions
    ``[pos0[b], pos0[b]+C)``; causal + suffix-validity masking happens in
    the kernel against the scalar-prefetched ``pos0`` / ``seq_lens``, so
    prefix-cache suffix-only prefill (``pos0`` mid-page included) needs no
    gathered view.  The chunk axis is padded to a ``block_q`` multiple
    in here; padded rows are sliced off before returning.
    """
    if attn_backend not in ("pallas_interpret", "pallas_tpu"):
        raise ValueError(
            f"paged_prefill_attention runs the fused kernel only "
            f"(pallas_interpret/pallas_tpu); got {attn_backend!r}")
    b, c, hq, d = q.shape
    hkv = k_pages.shape[0]
    g = hq // hkv
    block_q = min(c, PREFILL_BLOCK_Q)
    cp = -(-c // block_q) * block_q
    qg = q.reshape(b, c, hkv, g, d).transpose(0, 2, 1, 3, 4)
    if cp != c:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, cp - c), (0, 0), (0, 0)))
    win = jnp.asarray(window, jnp.int32).reshape(1)
    interpret = attn_backend == "pallas_interpret"
    if mesh is not None:
        from repro.engine.sharded import sharded_paged_attention

        out = sharded_paged_attention(
            mesh, model_axis, qg, k_pages, v_pages, block_tables,
            pos0, win, k_scale, v_scale, interpret=interpret,
            prefill=dict(seq_lens=seq_lens, chunk=c, block_q=block_q))
    else:
        out = paged_prefill_pallas(
            qg, k_pages, v_pages, block_tables, pos0, seq_lens, win,
            k_scale, v_scale, chunk=c, block_q=block_q,
            interpret=interpret)
    out = out[:, :, :c].transpose(0, 2, 1, 3, 4)
    return out.reshape(b, c, hq, d).astype(q.dtype)


def synthetic_paged_case(rng, *, batch: int, nblk: int, page: int,
                         hkv: int, group: int, dh: int, kv_bits: int):
    """One synthetic (q, pools, block tables) decode case — the shared
    fixture of ``benchmarks/attn_bench.py`` and the paged rows of
    ``benchmarks/kernel_bench.py``, so both benches measure identical
    inputs.  ``rng``: a ``numpy.random.Generator``.  Returns a dict with
    ``q / k_pages / v_pages / k_scale / v_scale / block_tables``
    (head-major pools; scales None unless ``kv_bits``); block tables are
    a permutation of ``batch * nblk`` distinct non-null pages."""
    import numpy as np

    n_pages = batch * nblk + 1
    if kv_bits:
        kp = jnp.asarray(rng.integers(-127, 128, (hkv, n_pages, page, dh)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (hkv, n_pages, page, dh)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.004, 0.02, (hkv, n_pages, page)),
                         jnp.bfloat16)
        vs = jnp.asarray(rng.uniform(0.004, 0.02, (hkv, n_pages, page)),
                         jnp.bfloat16)
    else:
        kp = jnp.asarray(rng.standard_normal((hkv, n_pages, page, dh))
                         .astype(np.float32))
        vp = jnp.asarray(rng.standard_normal((hkv, n_pages, page, dh))
                         .astype(np.float32))
        ks = vs = None
    return {
        "q": jnp.asarray(rng.standard_normal((batch, 1, hkv * group, dh))
                         .astype(np.float32)),
        "k_pages": kp,
        "v_pages": vp,
        "k_scale": ks,
        "v_scale": vs,
        "block_tables": jnp.asarray(
            1 + rng.permutation(batch * nblk).reshape(batch, nblk),
            jnp.int32),
    }


def synthetic_prefill_case(rng, *, batch: int, nblk: int, page: int,
                           hkv: int, group: int, dh: int, chunk: int,
                           kv_bits: int):
    """A synthetic chunked-prefill case on top of :func:`synthetic_paged_case`
    pools: every lane has ``pos0`` tokens already resident (mid-page — not
    page-aligned — for ragged coverage) and prefills ``chunk`` more, the
    last lane's chunk ending short of the chunk boundary (``seq_lens <
    pos0 + chunk``).  The chunk's K/V is treated as already scattered: the
    pools hold all positions, exactly what both read paths see."""
    import numpy as np

    case = synthetic_paged_case(rng, batch=batch, nblk=nblk, page=page,
                                hkv=hkv, group=group, dh=dh,
                                kv_bits=kv_bits)
    t = nblk * page
    pos0 = np.minimum(np.maximum(t - chunk - 1, 0),
                      rng.integers(1, max(2, t - chunk + 1), (batch,)))
    seq = pos0 + chunk
    if batch > 1:
        seq[-1] = pos0[-1] + max(1, chunk - 1)  # ragged last lane
    case["q"] = jnp.asarray(
        rng.standard_normal((batch, chunk, hkv * group, dh))
        .astype(np.float32))
    case["pos0"] = jnp.asarray(pos0, jnp.int32)
    case["seq_lens"] = jnp.asarray(seq, jnp.int32)
    return case


