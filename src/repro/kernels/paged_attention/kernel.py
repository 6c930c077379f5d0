"""Paged attention Pallas kernels (decode + chunked prefill): read KV
pages *in place*.

The gather-then-attend read path (``models.attention.gather_kv_pages`` +
``attend_decode``) materializes every lane's full logical KV view —
``(B, n_blocks·page, Hkv, Dh)`` per layer, ×2 for K/V, ×2 again for the
scale pools on the int8 path — before a single score is computed, so HBM
traffic per decode token is ~3× the logical view (pool read + view write +
view read).  This kernel is the compute-in-place fix, the serving-side twin
of the paper's GEMV-at-BRAM-speed argument: the **block table drives the
K/V BlockSpec index maps** (scalar-prefetched, so the page id is known
before the DMA is issued), pages stream VMEM-ward exactly once per
(lane, kv head), and scores / running softmax statistics / the output
accumulator never leave VMEM.

Structure (same online-softmax pattern as ``kernels.flash_attention``):

* grid ``(B, Hkv, n_blocks)`` with the block-table walk innermost; the
  output block is revisited across that sweep and the (m, l) running
  statistics live in VMEM scratch.
* the pool is **head-major**, ``(Hkv, P, page, Dh)`` per layer, so one
  (head, page) block is ``(1, 1, page, Dh)``: its last two dims are the
  array's own, which is what the TPU's block-shape rule asks for (a
  ``(1, page, 1, Dh)`` slice of a ``(P, page, Hkv, Dh)`` pool is refused).
  Scale pools ``(Hkv, P, page)`` enter as ``(Hkv, P, 1, page)`` for the
  same reason: one page of scales is then a lane-dense ``(1, page)`` row.
* GQA rides in the Q layout: queries arrive as ``(B, Hkv, G, Dh)`` so one
  grid step attends all ``G = Hq // Hkv`` query heads of its KV head
  against one page.
* causal + sliding-window bounds are computed from the block index and the
  scalar-prefetched ``cur_pos`` / ``window`` — no mask tensors exist
  anywhere, and ``window`` stays a *runtime* scalar so one compiled kernel
  serves every layer of a local/global stack under ``lax.scan``.  The
  window is folded into a scalar lower bound, so the vector mask is two
  compares and an ``and`` (no select over boolean vectors, which Mosaic
  does not lower).
* ``kv_bits=8`` pools dequantize in VMEM by folding the scale pools into
  the probabilities (``scores·s_k[t]``, ``p·s_v[t]`` — the same math as
  ``attend_decode_quant``), so the pool bytes stay 1 byte/element all the
  way to VMEM.

Both contractions take their operands in the pool's storage dtype (bf16
for int8 pools, whose values bf16 holds exactly) with f32 accumulation —
the products the gather path's ``preferred_element_type=f32`` einsums
form, so the two read paths differ only in summation order.

:func:`paged_prefill_pallas` is the chunked-prefill twin: grid
``(B, Hkv, q_blocks, kv_blocks)`` with the block-table walk innermost, the
whole query chunk riding as ``block_q·G`` rows per step, and the
per-lane chunk offsets (``pos0`` — tokens already resident from earlier
chunks or a prefix-cache hit, ``seq_lens`` — total valid after this chunk)
as scalar-prefetch operands, so causal masking over the unmatched suffix
happens against *logical* positions while K/V still stream straight from
pool pages.  This is what lets ``models.transformer.prefill_chunk`` stop
materializing the gathered ``(B, T, Hkv, Dh)`` view per layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NO_WINDOW = 1 << 30  # lower-bound offset that masks nothing


def _window_floor(cur, win):
    """Largest masked position below ``cur``: ``cur - win`` for a window,
    far below every position when ``win <= 0`` (full attention).  Scalar
    arithmetic only, so the vector mask needs no boolean select."""
    return cur - win - (win <= 0).astype(jnp.int32) * _NO_WINDOW


def _operand_dtype(ref, quant: bool):
    return jnp.bfloat16 if quant else ref.dtype


def _online_softmax_step(s, v, vs, o_ref, m_ref, l_ref, *, last, quant,
                         acc_dt):
    """Fold one block of scores ``s (R, page)`` and values ``v (page, D)``
    into the running (m, l, o) state; normalize on the ``last`` step."""
    m_old = m_ref[...]                                      # (R, 1)
    l_old = l_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new)                                  # (R, page)
    l_new = l_old * corr + jnp.sum(p, axis=-1, keepdims=True)
    if quant:
        p = p * vs
    pv = jax.lax.dot_general(p.astype(acc_dt), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    o_new = o_ref[...] * corr + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(last)
    def _final():
        o_ref[...] = o_new / jnp.maximum(l_new, 1e-30)

    @pl.when(jnp.logical_not(last))
    def _accum():
        o_ref[...] = o_new


def _body(bt_ref, pos_ref, win_ref, q_ref, k_ref, v_ref,
          ks_ref, vs_ref, o_ref, m_ref, l_ref, *,
          scale: float, page: int, n_blocks: int, quant: bool):
    """One (lane, kv-head, logical-block) step of the online softmax.

    ``ks_ref`` / ``vs_ref`` are the ``(1, 1, 1, page)`` scale blocks (None
    when the pool is full precision).  The ``(1, 1, G, D)`` output block
    is revisited across the innermost grid dimension (the block-table
    walk); the running (m, l) statistics live in VMEM scratch and never
    touch HBM."""
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    acc_dt = _operand_dtype(k_ref, quant)
    q = q_ref[0, 0].astype(acc_dt)                          # (G, D)
    k = k_ref[0, 0].astype(acc_dt)                          # (page, D)
    v = v_ref[0, 0].astype(acc_dt)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    vs = None
    if quant:
        s = s * ks_ref[0, 0].astype(jnp.float32)            # (1, page)
        vs = vs_ref[0, 0].astype(jnp.float32)

    # causal + sliding-window bounds: pool row t of this block holds
    # logical position i*page + t
    cur = pos_ref[b]
    lo = _window_floor(cur, win_ref[0])
    kv_pos = i * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    mask = jnp.logical_and(kv_pos <= cur, kv_pos > lo)
    s = jnp.where(mask, s, NEG_INF)

    _online_softmax_step(s, v, vs, o_ref.at[0, 0], m_ref, l_ref,
                         last=i == n_blocks - 1, quant=quant, acc_dt=acc_dt)


def _kernel_quant(bt_ref, pos_ref, win_ref, q_ref, k_ref, v_ref,
                  ks_ref, vs_ref, o_ref, m_ref, l_ref, **kw):
    _body(bt_ref, pos_ref, win_ref, q_ref, k_ref, v_ref,
          ks_ref, vs_ref, o_ref, m_ref, l_ref, quant=True, **kw)


def _kernel_full(bt_ref, pos_ref, win_ref, q_ref, k_ref, v_ref,
                 o_ref, m_ref, l_ref, **kw):
    _body(bt_ref, pos_ref, win_ref, q_ref, k_ref, v_ref,
          None, None, o_ref, m_ref, l_ref, quant=False, **kw)


def _scale_rows(scale_pool):
    """``(Hkv, P, page)`` -> ``(Hkv, P, 1, page)``: one page of scales is
    then a full-extent ``(1, page)`` block."""
    h, p, page = scale_pool.shape
    return scale_pool.reshape(h, p, 1, page)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(
    q: jnp.ndarray,            # (B, Hkv, G, Dh) — grouped query layout
    k_pages: jnp.ndarray,      # (Hkv, P, page, Dh) — one layer's pool
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, n_blocks) int32
    cur_pos: jnp.ndarray,      # (B,) int32 position of the newest token
    window: jnp.ndarray,       # (1,) int32 (runtime scalar; <= 0 = full)
    k_scale=None,              # (Hkv, P, page) — int8 pools only
    v_scale=None,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Fused paged decode attention; returns ``(B, Hkv, G, Dh)`` float32.

    The block table and the masking scalars travel as scalar-prefetch
    operands (``pltpu.PrefetchScalarGridSpec``): index maps see them before
    the grid step's DMAs are issued, which is what lets the K/V BlockSpecs
    address pool pages directly — the gathered copy never exists.
    """
    b, hkv, g, d = q.shape
    page = k_pages.shape[2]
    n_blocks = block_tables.shape[1]
    scale = d ** -0.5
    quant = k_scale is not None

    def _at_page(bb, h, i, bt, pos, win):
        return (h, bt[bb, i], 0, 0)

    def _at_q(bb, h, i, bt, pos, win):
        return (bb, h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, d), _at_q),
        pl.BlockSpec((1, 1, page, d), _at_page),
        pl.BlockSpec((1, 1, page, d), _at_page),
    ]
    operands = [q, k_pages, v_pages]
    kernel = _kernel_full
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, page), _at_page),
                     pl.BlockSpec((1, 1, 1, page), _at_page)]
        operands += [_scale_rows(k_scale), _scale_rows(v_scale)]
        kernel = _kernel_quant

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, d), _at_q),
        # running (max, sumexp) stay in VMEM across the block-table walk —
        # they are softmax bookkeeping, not results, and never touch HBM
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, page=page,
                          n_blocks=n_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
        interpret=interpret,
        name="paged_attention_decode",
    )(block_tables.astype(jnp.int32), cur_pos.astype(jnp.int32),
      window, *operands)


# ---------------------------------------------------------------------------
# chunked-prefill mode: query blocks × KV blocks
# ---------------------------------------------------------------------------


def _row_offsets(rows: int, group: int):
    """Chunk offset ``r // group`` of each flattened query row, as an
    ``(rows, 1)`` int32 column.  Vector integer division does not lower
    on the TPU, so it is taken in f32 with a half-row margin (exact for
    any row count below 2^20)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0).astype(jnp.float32)
    return ((r + 0.5) * (1.0 / group)).astype(jnp.int32)


def _prefill_body(bt_ref, pos0_ref, seq_ref, win_ref, q_ref, k_ref, v_ref,
                  ks_ref, vs_ref, o_ref, m_ref, l_ref, *,
                  scale: float, page: int, n_blocks: int, block_q: int,
                  group: int, chunk: int, quant: bool):
    """One (lane, kv-head, q-block, logical-kv-block) step.

    The query block carries ``block_q`` chunk positions × ``group`` GQA
    heads flattened to ``R = block_q·G`` rows; row ``r`` is chunk offset
    ``r // G``, so its logical position is ``pos0[b] + iq·block_q + r//G``.
    Valid KV for a row is the causal range below that position clipped to
    ``limit = min(seq_lens[b], pos0[b] + chunk)`` — exactly the
    ``kv_valid`` mask of the gather path (padded queries beyond ``limit``
    still attend the lane's valid prefix, matching ``attend_dense``)."""
    bb = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    r = block_q * group

    @pl.when(ik == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    acc_dt = _operand_dtype(k_ref, quant)
    q = q_ref[0, 0].astype(acc_dt)                          # (R, D)
    k = k_ref[0, 0].astype(acc_dt)                          # (page, D)
    v = v_ref[0, 0].astype(acc_dt)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    vs = None
    if quant:
        s = s * ks_ref[0, 0].astype(jnp.float32)
        vs = vs_ref[0, 0].astype(jnp.float32)

    # logical positions: query row r sits at pos0 + iq*block_q + r//G,
    # pool row t of this block at ik*page + t
    pos0 = pos0_ref[bb]
    q_pos = pos0 + iq * block_q + _row_offsets(r, group)     # (R, 1)
    kv_pos = ik * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    limit = jnp.minimum(seq_ref[bb], pos0 + chunk)
    lo = _window_floor(q_pos, win_ref[0])
    mask = jnp.logical_and(kv_pos <= q_pos, kv_pos < limit)
    mask = jnp.logical_and(mask, kv_pos > lo)
    s = jnp.where(mask, s, NEG_INF)

    _online_softmax_step(s, v, vs, o_ref.at[0, 0], m_ref, l_ref,
                         last=ik == n_blocks - 1, quant=quant,
                         acc_dt=acc_dt)


def _pf_kernel_quant(bt_ref, pos0_ref, seq_ref, win_ref, q_ref, k_ref,
                     v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, **kw):
    _prefill_body(bt_ref, pos0_ref, seq_ref, win_ref, q_ref, k_ref, v_ref,
                  ks_ref, vs_ref, o_ref, m_ref, l_ref, quant=True, **kw)


def _pf_kernel_full(bt_ref, pos0_ref, seq_ref, win_ref, q_ref, k_ref,
                    v_ref, o_ref, m_ref, l_ref, **kw):
    _prefill_body(bt_ref, pos0_ref, seq_ref, win_ref, q_ref, k_ref, v_ref,
                  None, None, o_ref, m_ref, l_ref, quant=False, **kw)


@functools.partial(jax.jit, static_argnames=("chunk", "block_q",
                                             "interpret"))
def paged_prefill_pallas(
    q: jnp.ndarray,            # (B, Hkv, Cp, G, Dh) — grouped chunk queries
    k_pages: jnp.ndarray,      # (Hkv, P, page, Dh) — one layer's pool
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, n_blocks) int32
    pos0: jnp.ndarray,         # (B,) int32 tokens already resident
    seq_lens: jnp.ndarray,     # (B,) int32 total valid after this chunk
    window: jnp.ndarray,       # (1,) int32 (runtime scalar; <= 0 = full)
    k_scale=None,              # (Hkv, P, page) — int8 pools only
    v_scale=None,
    *,
    chunk: int,                # true (unpadded) chunk length C
    block_q: int,
    interpret: bool,
) -> jnp.ndarray:
    """Fused paged chunked-prefill attention; ``(B, Hkv, Cp, G, Dh)`` f32.

    ``q``'s chunk axis ``Cp`` must be a ``block_q`` multiple (the ops
    wrapper pads; padded rows attend the lane's valid prefix and are
    sliced off outside).  The chunk's own K/V must already be scattered
    into the pool — the kernel is a pure read path, like decode.  The
    (chunk, head) axes enter the kernel flattened to rows ``c·G + g``, so
    a query block is a 2-D ``(block_q·G, Dh)`` tile.
    """
    b, hkv, cp, g, d = q.shape
    page = k_pages.shape[2]
    n_blocks = block_tables.shape[1]
    nq = cp // block_q
    r = block_q * g
    scale = d ** -0.5
    quant = k_scale is not None

    def _at_page(bb, h, iq, ik, bt, pos0, seq, win):
        return (h, bt[bb, ik], 0, 0)

    def _at_q(bb, h, iq, ik, bt, pos0, seq, win):
        return (bb, h, iq, 0)

    in_specs = [
        pl.BlockSpec((1, 1, r, d), _at_q),
        pl.BlockSpec((1, 1, page, d), _at_page),
        pl.BlockSpec((1, 1, page, d), _at_page),
    ]
    operands = [q.reshape(b, hkv, cp * g, d), k_pages, v_pages]
    kernel = _pf_kernel_full
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, page), _at_page),
                     pl.BlockSpec((1, 1, 1, page), _at_page)]
        operands += [_scale_rows(k_scale), _scale_rows(v_scale)]
        kernel = _pf_kernel_quant

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, hkv, nq, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, r, d), _at_q),
        scratch_shapes=[
            pltpu.VMEM((r, 1), jnp.float32),
            pltpu.VMEM((r, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel, scale=scale, page=page,
                          n_blocks=n_blocks, block_q=block_q, group=g,
                          chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, cp * g, d), jnp.float32),
        interpret=interpret,
        name="paged_attention_prefill",
    )(block_tables.astype(jnp.int32), pos0.astype(jnp.int32),
      seq_lens.astype(jnp.int32), window, *operands)
    return out.reshape(b, hkv, cp, g, d)
