"""Sharding hints: best-effort ``with_sharding_constraint`` wrappers.

Model code calls these unconditionally; they are no-ops unless a mesh
context is active (:func:`use_mesh`), and they silently drop any axis name
the active mesh does not have or that does not divide the corresponding
array dimension.  That lets one model implementation run unchanged on a
single CPU device, an 8-device host test mesh, and the 512-chip dry-run.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

AxisEntry = Union[None, str, Sequence[str]]


def active_mesh():
    """The mesh entered via :func:`use_mesh`, or None."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def use_mesh(mesh):
    """Mesh context manager (``jax.sharding.set_mesh``).  Model-internal
    sharding hints (:func:`with_hint`) only fire inside it."""
    return jax.sharding.set_mesh(mesh)


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with ``Auto`` axes: the compiler propagates
    shardings from the placed arrays and the hints, as every caller here
    expects (``jax.make_mesh``'s default ``Explicit`` axes would demand a
    sharding on every intermediate)."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def _filter_entry(entry: AxisEntry, dim: int, axes: dict) -> AxisEntry:
    """Keep only axis names that exist and whose product divides ``dim``."""
    if entry is None:
        return None
    names = (entry,) if isinstance(entry, str) else tuple(entry)
    kept = []
    prod = 1
    for n in names:
        size = axes.get(n)
        if size is None:
            continue
        if dim % (prod * size) != 0:
            continue
        kept.append(n)
        prod *= size
    if not kept:
        return None
    return kept[0] if len(kept) == 1 else tuple(kept)


def with_hint(x: jnp.ndarray, *entries: AxisEntry) -> jnp.ndarray:
    """Constrain ``x``'s sharding to ``P(*entries)`` where possible.

    Each positional entry maps to one leading dimension of ``x`` (missing
    trailing entries mean replicated).  Unknown axes and non-divisible
    dimensions degrade to replication instead of erroring.
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    axes = dict(mesh.shape)  # name -> size; works for Mesh and AbstractMesh
    spec = [
        _filter_entry(e, x.shape[i], axes)
        for i, e in enumerate(entries[: x.ndim])
    ]
    spec += [None] * (x.ndim - len(spec))
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def shard_batch_seq(x: jnp.ndarray) -> jnp.ndarray:
    """(B, S, D) activations: batch over the data axes, rest replicated."""
    return with_hint(x, ("pod", "data"))


def shard_experts(x: jnp.ndarray) -> jnp.ndarray:
    """Expert-stacked tensor: the E axis over the ``model`` mesh axis.

    Accepts ``(E, C, D)`` or batched ``(B, E, C, D)`` dispatch buffers.
    """
    if x.ndim >= 4:
        return with_hint(x, None, "model")
    return with_hint(x, "model")
