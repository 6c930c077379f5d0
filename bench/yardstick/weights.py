"""Random weights from ``--seed``: the leaf maker every architecture module
of ``bench/yardstick/arch`` builds its parameter tree with.

Every value is a function of (seed, leaf path, layer) alone, so the
float32 reference can make any one layer again by itself, without taking
anything the program holds, and a leaf keeps its values when other leaves
are added beside it.  The tree's layout is the program's interface and
belongs to the architecture module; the values are the benchmark's.
"""

from __future__ import annotations

import zlib
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

# one leaf: (path, shape, standard deviation)
Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], float]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned integer")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _value(key, path, index, shape, std, dtype):
    tag = zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF
    k = jax.random.fold_in(jax.random.fold_in(key, tag), index)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def build(leaves: Sequence[Leaf], key, index, dtype) -> Dict:
    """The nested dict of ``leaves``, each drawn N(0, std^2) for layer
    ``index`` (0 for leaves outside the layers) and cast to ``dtype``."""
    out: Dict = {}
    for path, shape, std in leaves:
        tree = out
        for p in path[:-1]:
            tree = tree.setdefault(p, {})
        tree[path[-1]] = _value(key, path, index, shape, std, dtype)
    return out
