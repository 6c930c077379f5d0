"""Sharding rules: name-based tensor-parallel specs for every param tree
in the zoo, plus batch / optimizer-state / KV-cache shardings.

The rules are *name-and-shape* driven, not architecture driven: a leaf's
key path decides the candidate axis (column-parallel QKV/up projections
shard their output axis, row-parallel out/down projections shard their
input axis, stacked MoE experts shard the expert axis), and a divisibility
check against the mesh decides whether the shard actually happens —
non-divisible dimensions degrade to replication, never error.

All functions accept any object with ``axis_names`` and a ``shape``
name->size mapping (a real ``jax.sharding.Mesh`` or a test stand-in).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

Pytree = Any

# column-parallel (shard the output-feature axis): activations stay
# replicated, outputs become model-sharded.
_COL = {"wq", "wk", "wv", "w_gate", "w_up", "in_proj", "lm_head"}
# row-parallel (shard the input-feature axis): consumes model-sharded
# activations, XLA inserts the reduce.
_ROW = {"wo", "w_down", "out_proj"}
_SPECIAL = {"embed"}
# engine PackedLinear leaves ride the rules of their owning linear.
_ENGINE_LEAVES = {"packed", "scale", "bias", "w"}

# decode-cache leaves, by key name.  The stacked slot cache
# (L, B, T, Hkv, Dh) shards axis 1 (batch lanes) over the data axes and the
# KV-head axis over ``model``; the head-major KVPages pool has its own rule
# (``_pool_shardings``).
_ATTN_KV_KEYS = {"k", "v", "k_global", "v_global", "k_local", "v_local"}
# int8-cache / quantized-page scale pools: trailing axis is the KV-head
# axis and must follow its K/V pool's head sharding.
_KV_SCALE_KEYS = {"k_scale", "v_scale"}
# host-built paged-serving index state (block tables, per-lane positions,
# lane-activity masks): lane axis over the data axes, never ``model``.
_PAGE_STATE_KEYS = {"block_tables", "pos", "active"}

_STACKED_CACHE_KEYS = (
    _ATTN_KV_KEYS | _KV_SCALE_KEYS | {"conv", "h"} | _PAGE_STATE_KEYS
)


def _key_str(k) -> str:
    if isinstance(k, jax.tree_util.DictKey):
        return str(k.key)
    if isinstance(k, jax.tree_util.GetAttrKey):
        return k.name
    if isinstance(k, jax.tree_util.SequenceKey):
        return str(k.idx)
    return str(k)


def _mesh_sizes(mesh) -> dict:
    shape = mesh.shape
    return dict(shape)


def _data_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _divisible_prefix(dim: int, axes: Tuple[str, ...], sizes: dict):
    """Longest prefix of ``axes`` whose size product divides ``dim``."""
    kept, prod = [], 1
    for a in axes:
        if dim <= 0 or dim % (prod * sizes[a]) != 0:
            break
        kept.append(a)
        prod *= sizes[a]
    return tuple(kept)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def param_spec(path, leaf, mesh, model_axis: str = "model") -> P:
    """Tensor-parallel PartitionSpec for one param leaf, by key path."""
    ndim = getattr(leaf, "ndim", 0)
    spec = [None] * ndim
    sizes = _mesh_sizes(mesh)
    msize = sizes.get(model_axis)
    if not msize or ndim == 0:
        return P(*spec)

    names = [_key_str(k) for k in path]
    leafname = names[-1] if names else ""
    owner = next(
        (n for n in reversed(names) if n in _COL | _ROW | _SPECIAL), None)
    if owner is None:
        return P(*spec)

    def put(ax: int):
        ax %= ndim
        if leaf.shape[ax] > 0 and leaf.shape[ax] % msize == 0:
            spec[ax] = model_axis

    stacked_experts = (
        "moe" in names
        and "shared" not in names
        and owner in _COL | _ROW
        and leafname in (owner, "packed", "scale")
        and ndim >= 3
    )
    if owner == "embed":
        if ndim >= 2:
            put(-2)  # vocab axis: (vocab, d) or audio (K, vocab, d)
    elif stacked_experts:
        put(ndim - 3)  # the expert axis of (..., E, D_in, D_out)
    elif owner in _COL:
        put(-1)
    elif owner in _ROW:
        if leafname == "bias":
            pass  # row-parallel bias spans the full output axis
        elif ndim >= 2:
            put(-2)
    return P(*spec)


def _with_fsdp(spec: P, leaf, mesh) -> P:
    """Layer ZeRO/FSDP on top of TP: shard the first still-replicated,
    divisible axis over the data axes (params + optimizer state of 100B+
    configs cannot fit TP-only)."""
    data_axes = _data_axes(mesh)
    if not data_axes:
        return spec
    sizes = _mesh_sizes(mesh)
    prod = 1
    for a in data_axes:
        prod *= sizes[a]
    if prod == 1:
        return spec
    entries = list(spec) + [None] * (leaf.ndim - len(spec))
    for ax in range(leaf.ndim):
        if entries[ax] is None and leaf.shape[ax] > 0 \
                and leaf.shape[ax] % prod == 0:
            entries[ax] = data_axes if len(data_axes) > 1 else data_axes[0]
            return P(*entries)
    return spec


def param_shardings(mesh, params: Pytree, mode: str = "tp") -> Pytree:
    """NamedSharding tree for a param tree.  ``mode``: "tp" | "fsdp"."""

    def one(path, leaf):
        spec = param_spec(path, leaf, mesh)
        if mode == "fsdp":
            spec = _with_fsdp(spec, leaf, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def opt_state_shardings(mesh, opt_state: Pytree, mode: str = "tp") -> Pytree:
    """Optimizer/EF state shardings: moment trees mirror the param tree's
    key names, so the same name-based rules apply; scalars replicate."""
    return param_shardings(mesh, opt_state, mode=mode)


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------


def batch_shardings(mesh, batch: Pytree) -> Pytree:
    """Batch-axis sharding over the data axes (``("pod", "data")`` when the
    pod axis carries data parallelism)."""
    data_axes = _data_axes(mesh)
    sizes = _mesh_sizes(mesh)

    def one(leaf):
        ndim = getattr(leaf, "ndim", 0)
        if ndim == 0 or not data_axes:
            return NamedSharding(mesh, P(*([None] * ndim)))
        kept = _divisible_prefix(leaf.shape[0], data_axes, sizes)
        spec = [kept if kept else None] + [None] * (ndim - 1)
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, batch)


def cache_shardings(mesh, cache: Pytree) -> Pytree:
    """Decode-cache shardings: the batch (slot) axis over the data axes and
    KV heads over the model axis when divisible.

    Handles the stacked ``(L, B, ...)`` layout, the unstacked
    tuple-of-``(B, ...)`` production layout, *and* the paged-serving
    :class:`~repro.serve.pages.KVPages` pytree: its head-major ``(L, Hkv,
    P, page, Dh)`` pools shard heads-over-``model`` and pages-over-data,
    its ``(L, Hkv, P, page)`` scale pools follow their K/V pool, and
    block tables / positions / activity masks shard their lane axis over
    the data axes only (they are host-built index state).
    """
    if hasattr(cache, "page_size"):  # a KVPages pool
        return _pool_shardings(mesh, cache)
    data_axes = _data_axes(mesh)
    sizes = _mesh_sizes(mesh)
    msize = sizes.get("model", 0)

    def one(path, leaf):
        ndim = getattr(leaf, "ndim", 0)
        spec = [None] * ndim
        if ndim == 0:
            return NamedSharding(mesh, P())
        names = [_key_str(k) for k in path]
        # innermost cache-key name wins, so a cache dict nested inside a
        # bigger serve-state tree keeps its rules.
        name = next(
            (n for n in reversed(names) if n in _STACKED_CACHE_KEYS),
            names[-1] if names else "")
        unstacked = any(
            isinstance(k, jax.tree_util.SequenceKey) for k in path)
        batch_ax = 0 if (name in _PAGE_STATE_KEYS or unstacked
                         or ndim < 2) else 1
        kept = _divisible_prefix(leaf.shape[batch_ax], data_axes, sizes)
        if kept:
            spec[batch_ax] = kept
        head_ax = None
        if name in _ATTN_KV_KEYS and ndim >= 4:
            head_ax = -2                  # (..., T/page, Hkv, Dh)
        elif name in _KV_SCALE_KEYS and ndim >= 3:
            head_ax = -1                  # (..., T/page, Hkv)
        if (head_ax is not None and msize and leaf.shape[head_ax] > 0
                and leaf.shape[head_ax] % msize == 0):
            spec[head_ax] = "model"       # KV-head axis
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, cache)


def _pool_shardings(mesh, pages) -> Pytree:
    """KVPages shardings: axis 1 (KV heads) over ``model`` when it
    divides, axis 2 (physical pages) over the data axes — K/V pools and
    their scale pools alike, so dequantization never desyncs."""
    sizes = _mesh_sizes(mesh)
    msize = sizes.get("model", 0)

    def one(leaf):
        spec = [None] * leaf.ndim
        if msize and leaf.shape[1] % msize == 0:
            spec[1] = "model"
        kept = _divisible_prefix(leaf.shape[2], _data_axes(mesh), sizes)
        if kept:
            spec[2] = kept
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, pages)


def paged_attn_partition(mesh, model_axis: str, n_kv_heads: int,
                         batch: int) -> Tuple[Any, Any]:
    """Spec entries for shard_mapping the fused paged-attention kernel.

    Returns ``(head_entry, lane_entry)`` — the PartitionSpec entries for
    the pool's KV-head axis and the per-lane axes (queries, block tables,
    positions).  Heads shard over ``model_axis`` exactly when it divides
    (matching :func:`cache_shardings`' heads-over-model placement, so the
    per-shard kernel sees the head slice its pool shard already holds);
    lanes shard over the data axes when the batch divides.  Anything
    non-divisible degrades to replication (None entry), mirroring the
    degrade discipline of the param specs — never an error.
    """
    sizes = _mesh_sizes(mesh)
    msize = sizes.get(model_axis, 1)
    head = (model_axis if msize > 1 and n_kv_heads > 0
            and n_kv_heads % msize == 0 else None)
    daxes = tuple(a for a in _data_axes(mesh) if a != model_axis)
    prod = 1
    for a in daxes:
        prod *= sizes[a]
    lane = None
    if prod > 1 and batch > 0 and batch % prod == 0:
        lane = daxes if len(daxes) > 1 else daxes[0]
    return head, lane


def pool_pages_for_mesh(n_pages: int, mesh) -> int:
    """Round a page-pool size up so the physical page axis shards evenly
    over the data axes.

    Pages-over-data needs ``n_pages`` divisible by the data-axes product
    (the null page makes natural pool sizes odd); padding only adds spare
    capacity — the allocator simply has more free pages.  ``mesh=None``
    (or no data axes) returns ``n_pages`` unchanged.
    """
    if mesh is None or n_pages <= 0:
        return n_pages
    sizes = _mesh_sizes(mesh)
    prod = 1
    for a in _data_axes(mesh):
        prod *= sizes[a]
    if prod <= 1:
        return n_pages
    return -(-n_pages // prod) * prod
