"""``sharded`` — the mesh-native engine backend.

IMAGine's core scaling claim is that GEMV throughput tracks the number of
memory banks holding weight bit-planes.  This backend is that claim at pod
scale: it ``shard_map``s a wrapped single-device backend
(``plan.inner_backend``) over the plan's ``model_axis``, so each device
owns a contiguous slice of the bit-packed weight and runs the GEMV for its
slice only — the Balanced-Data-Placement rule (rows spread over banks) and
the UPMEM lesson (reduce partials next to the data) in one dispatch entry.

Partitioning follows ``repro.dist.sharding``'s divisibility discipline
(:func:`repro.engine.packed.partition_kind`):

* **column-parallel** (preferred — no collective): the output-feature axis
  of ``packed``/``scale`` is sharded, activations are replicated, and the
  result reassembles model-sharded along its feature axis.
* **row-parallel**: the packed contraction axis and the activation feature
  axis are sharded; each device produces a partial GEMV reduced with
  :func:`repro.dist.collectives.psum_partial` (exact fp32 ``psum``, or
  ``compressed_psum_leaf`` codes when ``plan.psum_bits`` is 4/8).
* anything non-divisible — stacked expert weights, trivial meshes, a plan
  with no mesh — degrades to the wrapped backend unsharded, mirroring the
  degrade-to-replication rule of the param specs.  Never an error.

With ``psum_bits == 0`` both partitionings are bit-for-bit against the
wrapped backend whenever the per-slice fp32 accumulations are exact
(integer activation/weight grids — ``tests/test_shard_engine.py`` pins
this on an 8-device host mesh).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.collectives import psum_partial
from repro.dist.sharding import paged_attn_partition
from repro.engine.backends import get_backend, register_backend
from repro.engine.packed import PackedLinear, partition_kind


def _batch_entry(mesh, model_axis: str, x: jnp.ndarray):
    """Data-axes spec entry for x's leading (batch) axis, or None.

    Serving activations are lanes-over-data; declaring that in the
    shard_map specs keeps each data shard computing its own lanes instead
    of all-gathering the batch before every GEMV.  Degrades to
    replication when the batch does not divide (shard_map specs, unlike
    hints, hard-require divisibility).
    """
    sizes = dict(mesh.shape)
    daxes = tuple(a for a in ("pod", "data")
                  if a in sizes and a != model_axis)
    prod = 1
    for a in daxes:
        prod *= sizes[a]
    if x.ndim < 2 or prod <= 1 or x.shape[0] % prod != 0:
        return None
    return daxes if len(daxes) > 1 else daxes[0]


@register_backend("sharded")
def _sharded(plan, lin: PackedLinear, x: jnp.ndarray, out_dtype):
    inner = get_backend(plan.inner_backend or "reference")
    mesh, axis = plan.mesh, plan.model_axis
    msize = dict(mesh.shape).get(axis, 1) if mesh is not None else 1
    kind = partition_kind(lin, msize)
    if mesh is None or kind == "replicate":
        return inner(plan, lin, x, out_dtype)

    bits, k, n = lin.bits, lin.in_features, lin.out_features
    lead = (_batch_entry(mesh, axis, x),) + (None,) * (x.ndim - 2)

    if kind == "col":
        # W columns over the model axis: x replicated, no collective; the
        # output comes back model-sharded along its feature axis.
        def col(packed, scale, xx):
            loc = PackedLinear(packed, scale, None, bits, k, n // msize)
            return inner(plan, loc, xx, out_dtype)

        return jax.shard_map(
            col, mesh=mesh,
            in_specs=(P(None, axis), P(None, axis), P(*lead, None)),
            out_specs=P(*lead, axis),
            check_vma=False,
        )(lin.packed, lin.scale, x)

    # row-parallel: K (packed rows + activation features) over the model
    # axis; partial GEMVs accumulate in fp32 and reduce close to the data.
    def row(packed, scale, xx):
        loc = PackedLinear(packed, scale, None, bits, k // msize, n)
        part = inner(plan, loc, xx, jnp.float32)
        return psum_partial(part, axis, bits=plan.psum_bits).astype(
            out_dtype)

    return jax.shard_map(
        row, mesh=mesh,
        in_specs=(P(axis, None), P(None, None), P(*lead, axis)),
        out_specs=P(*lead, None),
        check_vma=False,
    )(lin.packed, lin.scale, x)


# ---------------------------------------------------------------------------
# fused paged attention under shard_map (decode + chunked prefill)
# ---------------------------------------------------------------------------


def sharded_paged_attention(mesh, model_axis, qg, k_pages, v_pages,
                            block_tables, pos, win, k_scale, v_scale, *,
                            interpret: bool, prefill=None):
    """shard_map the fused paged-attention kernel over the mesh.

    KV heads are already the ``model``-sharded dim of the page pool
    (``dist.sharding.cache_shardings``), and softmax is per-head, so each
    per-shard kernel invocation runs on the contiguous head slice its
    shard holds — no in-kernel collective.  Queries arrive grouped
    (decode ``(B, Hkv, G, D)``, prefill ``(B, Hkv, Cp, G, D)``): axis 1
    is the KV-head axis on both, so one head entry shards queries, pools
    and scale pools alike.  Lanes (queries, block tables, positions)
    shard over the data axes when the batch divides.

    The *page* axis stays replicated inside the kernel: a lane's block
    table may point at any physical page, so the pages-over-data placement
    is undone (an all-gather over the data axes within each model group)
    before the per-shard kernel runs — the same logical traffic the
    gather backend's cross-shard ``jnp.take`` pays, without the gathered
    view write/read.  Non-divisible heads/batch degrade to replication
    (``paged_attn_partition``), never an error.

    ``prefill``: None runs the decode kernel (``pos`` = ``cur_pos``);
    a ``dict(seq_lens=..., chunk=..., block_q=...)`` runs the prefill
    grid (``pos`` = ``pos0``).
    """
    from repro.kernels.paged_attention.kernel import (
        paged_attention_pallas,
        paged_prefill_pallas,
    )

    head, lane = paged_attn_partition(
        mesh, model_axis, k_pages.shape[0], qg.shape[0])
    q_tail = (None,) * (qg.ndim - 2)
    q_spec = P(lane, head, *q_tail)
    pool = P(head, None, None, None)      # head-major (Hkv, P, page, Dh)
    scale_p = P(head, None, None)         # (Hkv, P, page)
    bt_s, lane_s, win_s = P(lane, None), P(lane), P(None)
    quant = k_scale is not None

    if prefill is None:
        def run(qg, kp, vp, bt, pos, win, *scales):
            ks, vs = scales if quant else (None, None)
            return paged_attention_pallas(qg, kp, vp, bt, pos, win, ks, vs,
                                          interpret=interpret)

        in_specs = (q_spec, pool, pool, bt_s, lane_s, win_s)
        operands = (qg, k_pages, v_pages, block_tables, pos, win)
    else:
        seq_lens = prefill["seq_lens"]
        chunk, block_q = prefill["chunk"], prefill["block_q"]

        def run(qg, kp, vp, bt, pos, seq, win, *scales):
            ks, vs = scales if quant else (None, None)
            return paged_prefill_pallas(qg, kp, vp, bt, pos, seq, win,
                                        ks, vs, chunk=chunk,
                                        block_q=block_q,
                                        interpret=interpret)

        in_specs = (q_spec, pool, pool, bt_s, lane_s, lane_s, win_s)
        operands = (qg, k_pages, v_pages, block_tables, pos, seq_lens, win)
    if quant:
        in_specs = in_specs + (scale_p, scale_p)
        operands = operands + (k_scale, v_scale)

    return jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                         out_specs=q_spec, check_vma=False)(*operands)
