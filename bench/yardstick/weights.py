"""Random weights from ``--seed``, made on the device in the type they are
served in, laid out as the program's dense-decoder parameter tree.

Every value is a function of (seed, leaf name, layer) alone, so the
float32 reference can make any one layer again by itself, without taking
anything the program holds.  The layout (``{"w", "bias"}`` linears stored
``(in, out)``, norm scales stored as ``weight - 1``) is the program's
interface; the values are the benchmark's.
"""

from __future__ import annotations

import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.yardstick.model import Dims

# (path, shape-fn, std-fn): one layer's leaves.  Norm leaves hold the
# program's ``scale`` (the norm multiplies by ``1 + scale``).
_NORM_STD = 0.1
_BIAS_STD = 0.1
_EMBED_STD = 0.02


def _layer_leaves(d: Dims) -> List[Tuple[Tuple[str, ...], Tuple[int, ...], float]]:
    qo, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
    out = [(("ln1",), (d.d_model,), _NORM_STD),
           (("ln2",), (d.d_model,), _NORM_STD)]
    for name, n in (("wq", qo), ("wk", kv), ("wv", kv)):
        out.append((("attn", name, "w"), (d.d_model, n), d.d_model ** -0.5))
        if d.qkv_bias:
            out.append((("attn", name, "bias"), (n,), _BIAS_STD))
    out.append((("attn", "wo", "w"), (qo, d.d_model), qo ** -0.5))
    mlp = [("w_up", d.d_model, d.d_ff), ("w_down", d.d_ff, d.d_model)]
    if d.gated:
        mlp.append(("w_gate", d.d_model, d.d_ff))
    for name, k, n in mlp:
        out.append((("mlp", name, "w"), (k, n), k ** -0.5))
    return out


def _top_leaves(d: Dims):
    out = [(("embed",), (d.vocab, d.d_model), _EMBED_STD),
           (("final_norm",), (d.d_model,), _NORM_STD)]
    if not d.tied:
        out.append((("lm_head", "w"), (d.d_model, d.vocab),
                    d.d_model ** -0.5))
    return out


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


def _set(tree: Dict, path, value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def layer(d: Dims, key, index, dtype=None) -> Dict:
    """One layer's leaves (no leading layer axis), in ``dtype`` or the
    served type."""
    dtype = dtype or jnp.dtype(d.dtype)
    out: Dict = {}
    for path, shape, std in _layer_leaves(d):
        _set(out, path, _value(key, path, index, shape, std, dtype))
    return out


def top(d: Dims, key, dtype=None) -> Dict:
    """The embedding table, the final norm and an untied LM head."""
    dtype = dtype or jnp.dtype(d.dtype)
    out: Dict = {}
    for path, shape, std in _top_leaves(d):
        _set(out, path, _value(key, path, 0, shape, std, dtype))
    return out


def params(d: Dims, key, dtype=None) -> Dict:
    """The whole tree, layers stacked on a leading axis.  Call under
    ``jax.jit`` (with ``out_shardings`` on a mesh) so it is made on the
    device in one program."""
    tree = top(d, key, dtype)
    tree["layers"] = jax.vmap(lambda i: layer(d, key, i, dtype))(
        jnp.arange(d.n_layers, dtype=jnp.int32))
    return tree
