"""Gather-then-attend reference for the fused paged-attention kernel.

Standalone jnp twin of ``models.attention.attend_paged_decode``'s
``gather`` path (kept import-free of ``repro.models`` so kernel tests and
benches can diff the two without circular imports).  This is exactly the
traffic pattern the kernel exists to kill: ``jnp.take`` materializes the
``(B, n_blocks·page, Hkv, Dh)`` logical view per K/V (and per scale pool
on the int8 path) before a single score is computed.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gather(pages: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Head-major ``(Hkv, P, page, ...)`` pool -> ``(B, T, Hkv, ...)``."""
    g = jnp.take(pages, block_tables, axis=1)       # (Hkv, B, nblk, page, ...)
    hkv, b, nblk, page = g.shape[:4]
    g = g.reshape((hkv, b, nblk * page) + g.shape[4:])
    return jnp.moveaxis(g, 0, 2)


def paged_attention_ref(
    q: jnp.ndarray,            # (B, 1, Hq, Dh)
    k_pages: jnp.ndarray,      # (Hkv, P, page, Dh)
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, n_blocks) int32
    cur_pos: jnp.ndarray,      # (B,)
    window=0,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    b, _, hq, d = q.shape
    hkv = k_pages.shape[0]
    g = hq // hkv
    scale = d ** -0.5
    kg = _gather(k_pages, block_tables)             # (B, T, Hkv, Dh)
    vg = _gather(v_pages, block_tables)
    t = kg.shape[1]
    quant = k_scale is not None
    acc_in = jnp.bfloat16 if quant else kg.dtype
    qg = q.reshape(b, hkv, g, d).astype(acc_in)
    sc = jnp.einsum("bhgd,bkhd->bhgk", qg, kg.astype(acc_in),
                    preferred_element_type=jnp.float32) * scale
    if quant:
        ksg = _gather(k_scale, block_tables)        # (B, T, Hkv)
        sc = sc * ksg.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
    kv_pos = jnp.arange(t)[None, :]
    valid = kv_pos <= cur_pos[:, None]
    near = kv_pos > cur_pos[:, None] - window
    valid = jnp.logical_and(valid, jnp.where(window > 0, near, True))
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    if quant:
        vsg = _gather(v_scale, block_tables)
        p = p * vsg.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(acc_in),
                     vg.astype(acc_in),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def paged_prefill_ref(
    q: jnp.ndarray,            # (B, C, Hq, Dh) — one prefill chunk
    k_pages: jnp.ndarray,      # (Hkv, P, page, Dh)
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, n_blocks) int32
    pos0: jnp.ndarray,         # (B,) tokens already resident
    seq_lens: jnp.ndarray,     # (B,) total valid after this chunk
    window=0,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Gather-then-attend chunked prefill: the standalone twin of the
    ``attend_paged_prefill`` gather path (causal over logical positions,
    KV clipped to ``min(seq_lens, pos0 + C)``)."""
    b, c, hq, d = q.shape
    hkv = k_pages.shape[0]
    g = hq // hkv
    scale = d ** -0.5
    kg = _gather(k_pages, block_tables)             # (B, T, Hkv, Dh)
    vg = _gather(v_pages, block_tables)
    t = kg.shape[1]
    quant = k_scale is not None
    acc_in = jnp.bfloat16 if quant else jnp.float32
    qg = q.reshape(b, c, hkv, g, d).astype(acc_in)
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kg.astype(acc_in),
                    preferred_element_type=jnp.float32) * scale
    if quant:
        ksg = _gather(k_scale, block_tables)
        sc = sc * ksg.astype(jnp.float32).transpose(0, 2, 1)[:, :, None,
                                                             None, :]
    q_pos = pos0[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    kv_pos = jnp.arange(t, dtype=jnp.int32)[None, :]
    limit = jnp.minimum(seq_lens, pos0 + c)
    causal = kv_pos[:, None, :] <= q_pos[:, :, None]     # (B, C, T)
    near = kv_pos[:, None, :] > q_pos[:, :, None] - window
    mask = jnp.logical_and(causal, jnp.where(window > 0, near, True))
    mask = jnp.logical_and(mask, (kv_pos < limit[:, None])[:, None, :])
    sc = jnp.where(mask[:, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    if quant:
        vsg = _gather(v_scale, block_tables)
        p = p * vsg.astype(jnp.float32).transpose(0, 2, 1)[:, :, None,
                                                           None, :]
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(acc_in),
                     vg.astype(acc_in),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, c, hq, d).astype(q.dtype)
