"""The dense GQA decoder, as Qwen2's and StarCoder2's published
``config.json`` state it: everything the benchmark needs of this block.

- :func:`dims`: the sizes, read from a configuration file of
  ``bench/configs`` by its published keys and from nothing of the program;
- :func:`program_fields`: what the program's ``ModelConfig`` has to match;
- :func:`params`: the weights from the seed, in the program's tree layout;
- :func:`served_gaps`: the float32 reference;
- :func:`decode_step`, :func:`prefill_step`, :func:`attn_decode`,
  :func:`attn_prefill`, :func:`weight_bytes`: the serve path's modelled work.

The block: a norm before attention and before the MLP, RMSNorm or
LayerNorm with a bias (``norm_type``, epsilon ``rms_norm_eps`` or
``norm_epsilon``); rotary position embedding on the two halves of each
head (``rotate_half``); grouped-query attention with an optional sliding
window; SwiGLU (``hidden_act`` ``silu``) or a tanh-approximated GELU MLP;
a final norm and a tied or untied LM head.  Biases: ``attention_bias``
(Qwen2) puts one on q, k and v; ``use_bias`` (StarCoder2) on q, k, v, o
and every MLP linear.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from bench.yardstick import reference as R
from bench.yardstick import weights
from bench.yardstick.weights import Leaf


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool          # SwiGLU (gate, up, down) or a plain GELU MLP
    qkv_bias: bool       # bias on q, k and v
    out_bias: bool       # bias on o and on every MLP linear
    tied: bool           # LM head is the embedding table
    rope_theta: float
    norm: str            # "rms_norm" or "layer_norm" (weight and bias)
    norm_eps: float
    window: int          # sliding window in tokens, 0 = full attention
    dtype: str           # weights, KV and activations as served
    itemsize: int        # bytes per element of that type


_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
_NORMS = ("rms_norm", "layer_norm")


def dims(cfg: dict) -> Dims:
    """:class:`Dims` of one configuration file's contents."""
    act = cfg["hidden_act"]
    if act not in ("silu", "gelu_pytorch_tanh"):
        raise ValueError(f"no reference for hidden_act {act!r}")
    norm = cfg.get("norm_type", "rms_norm")
    if norm not in _NORMS:
        raise ValueError(f"no reference for norm_type {norm!r}")
    window = 0
    if cfg.get("use_sliding_window", True) and cfg.get("sliding_window"):
        window = int(cfg["sliding_window"])
    n_heads = int(cfg["num_attention_heads"])
    use_bias = bool(cfg.get("use_bias", False))
    return Dims(
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=n_heads,
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim")
                     or cfg["hidden_size"] // n_heads),
        d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]),
        gated=act == "silu",
        qkv_bias=bool(cfg.get("attention_bias", False)) or use_bias,
        out_bias=use_bias,
        tied=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        norm=norm,
        norm_eps=float(cfg.get("rms_norm_eps", cfg.get("norm_epsilon"))),
        window=window,
        dtype=cfg["torch_dtype"],
        itemsize=_ITEMSIZE[cfg["torch_dtype"]],
    )


def program_fields(mc, d: Dims) -> Dict[str, Tuple[object, object]]:
    """``name -> (the program's value, the file's)`` for every field of the
    program's ``ModelConfig`` that this block needs.  A program config
    without ``norm_type`` serves RMSNorm.  The program has no field for
    the biases on o and the MLP: its linears add a bias wherever the
    parameter tree holds one, and :func:`params` puts one there exactly
    when the file says so."""
    return {
        "family": (mc.family, "dense"),
        "num_hidden_layers": (mc.n_layers, d.n_layers),
        "hidden_size": (mc.d_model, d.d_model),
        "num_attention_heads": (mc.n_heads, d.n_heads),
        "num_key_value_heads": (mc.n_kv_heads, d.n_kv_heads),
        "head_dim": (mc.resolved_head_dim, d.head_dim),
        "intermediate_size": (mc.d_ff, d.d_ff),
        "vocab_size": (mc.vocab_size, d.vocab),
        "mlp gated": (mc.mlp_gated, d.gated),
        "qkv bias": (mc.qkv_bias, d.qkv_bias),
        "tie_word_embeddings": (mc.tie_embeddings, d.tied),
        "rope_theta": (mc.rope_theta, d.rope_theta),
        "norm_type": (getattr(mc, "norm_type", "rms_norm"), d.norm),
        "norm eps": (mc.norm_eps, d.norm_eps),
        "sliding_window": (mc.sliding_window if not mc.global_every
                           else -1, d.window),
        "itemsize": (_ITEMSIZE[mc.dtype], d.itemsize),
    }


# ---------------------------------------------------------------- weights
# Linears are ``{"w", "bias"}`` stored ``(in, out)``; a norm's weight is
# stored as ``weight - 1`` (the program multiplies by ``1 + scale``), and a
# LayerNorm's bias sits beside it as ``<norm>_bias``.
_NORM_STD = 0.1
_BIAS_STD = 0.1
_EMBED_STD = 0.02


def _norm_leaves(d: Dims, name: str) -> List[Leaf]:
    out = [((name,), (d.d_model,), _NORM_STD)]
    if d.norm == "layer_norm":
        out.append(((f"{name}_bias",), (d.d_model,), _BIAS_STD))
    return out


def _layer_leaves(d: Dims) -> List[Leaf]:
    qo, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
    out = _norm_leaves(d, "ln1") + _norm_leaves(d, "ln2")
    for name, n in (("wq", qo), ("wk", kv), ("wv", kv)):
        out.append((("attn", name, "w"), (d.d_model, n), d.d_model ** -0.5))
        if d.qkv_bias:
            out.append((("attn", name, "bias"), (n,), _BIAS_STD))
    out.append((("attn", "wo", "w"), (qo, d.d_model), qo ** -0.5))
    if d.out_bias:
        out.append((("attn", "wo", "bias"), (d.d_model,), _BIAS_STD))
    mlp = [("w_up", d.d_model, d.d_ff), ("w_down", d.d_ff, d.d_model)]
    if d.gated:
        mlp.append(("w_gate", d.d_model, d.d_ff))
    for name, k, n in mlp:
        out.append((("mlp", name, "w"), (k, n), k ** -0.5))
        if d.out_bias:
            out.append((("mlp", name, "bias"), (n,), _BIAS_STD))
    return out


def _top_leaves(d: Dims) -> List[Leaf]:
    out = [(("embed",), (d.vocab, d.d_model), _EMBED_STD)]
    out += _norm_leaves(d, "final_norm")
    if not d.tied:
        out.append((("lm_head", "w"), (d.d_model, d.vocab),
                    d.d_model ** -0.5))
    return out


def layer(d: Dims, key, index, dtype=None) -> Dict:
    """One layer's leaves (no leading layer axis), in ``dtype`` or the
    served type."""
    return weights.build(_layer_leaves(d), key, index,
                         dtype or jnp.dtype(d.dtype))


def top(d: Dims, key, dtype=None) -> Dict:
    """The embedding table, the final norm and an untied LM head."""
    return weights.build(_top_leaves(d), key, 0, dtype or jnp.dtype(d.dtype))


def params(d: Dims, key, dtype=None) -> Dict:
    """The whole tree, layers stacked on a leading axis.  Call under
    ``jax.jit`` (with ``out_shardings`` on a mesh) so it is made on the
    device in one program."""
    tree = top(d, key, dtype)
    tree["layers"] = jax.vmap(lambda i: layer(d, key, i, dtype))(
        jnp.arange(d.n_layers, dtype=jnp.int32))
    return tree


# -------------------------------------------------------------- reference
def _linear(p, x):
    y = R.mm(x, p["w"])
    return y + p["bias"] if "bias" in p else y


def _norm(d: Dims, x, p, name):
    w = 1.0 + p[name]
    if d.norm == "layer_norm":
        return R.layer_norm(x, w, p[f"{name}_bias"], d.norm_eps)
    return R.rms_norm(x, w, d.norm_eps)


def _block(d: Dims, q_block: int, x, lp, pos, seg):
    """One decoder layer over the packed rows ``x`` (T, d)."""
    t = x.shape[0]
    g = d.n_heads // d.n_kv_heads
    h = _norm(d, x, lp, "ln1")
    q = _linear(lp["attn"]["wq"], h).reshape(t, d.n_heads, d.head_dim)
    k = _linear(lp["attn"]["wk"], h).reshape(t, d.n_kv_heads, d.head_dim)
    v = _linear(lp["attn"]["wv"], h).reshape(t, d.n_kv_heads, d.head_dim)
    q, k = R.rope(q, pos, d.rope_theta), R.rope(k, pos, d.rope_theta)
    nb = t // q_block
    qb = q.reshape(nb, q_block, d.n_kv_heads, g, d.head_dim)

    def attend(args):
        qi, pq, sq = args                   # (Bq, Hkv, G, Dh), (Bq,), (Bq,)
        s = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=R.HI)
        s = s * d.head_dim ** -0.5
        ok = (sq[:, None] == seg[None, :]) & (pos[None, :] <= pq[:, None])
        if d.window:
            ok &= pos[None, :] > pq[:, None] - d.window
        s = jnp.where(ok[None, None], s, R.NEG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=R.HI)

    o = jax.lax.map(attend, (qb, pos.reshape(nb, q_block),
                             seg.reshape(nb, q_block)))
    x = x + _linear(lp["attn"]["wo"], o.reshape(t, d.n_heads * d.head_dim))
    h = _norm(d, x, lp, "ln2")
    m = lp["mlp"]
    if d.gated:
        a = jax.nn.silu(_linear(m["w_gate"], h)) * _linear(m["w_up"], h)
    else:
        a = R.gelu_tanh(_linear(m["w_up"], h))
    return x + _linear(m["w_down"], a)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run_layer(d: Dims, q_block: int, x, key, index, pos, seg):
    lp = R.f32(layer(d, key, index))
    return _block(d, q_block, x, lp, pos, seg)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(d: Dims, key, tokens):
    return R.f32(top(d, key))["embed"][tokens]


@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(d: Dims, key, x, rows, targets):
    """Per served row: reference best logit minus the served token's."""
    tp = R.f32(top(d, key))
    h = _norm(d, x[rows], tp, "final_norm")
    head = tp["embed"].T if d.tied else tp["lm_head"]["w"]
    logits = R.mm(h, head)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return best - got


def served_gaps(d: Dims, seed: int, seqs, *, tokens: int, rows: int,
                q_block: int, targets=None):
    """The gap of every served token of ``seqs`` (``(prompt, served)``
    pairs), in order; with ``targets``, the gap of those tokens at the
    same positions (see :func:`bench.yardstick.reference.pack`)."""
    key = weights.seed_key(seed)
    return R.gaps_by_layer(
        seqs, d.n_layers,
        lambda tok: _embed(d, key, tok),
        lambda x, i, pos, seg: _run_layer(d, q_block, x, key, i, pos, seg),
        lambda x, rows_, tgt: _gaps(d, key, x, rows_, tgt),
        tokens=tokens, rows=rows, targets=targets)


# ------------------------------------------------------------------ costs
# FLOPs and HBM bytes of the serve path's steps and attention kernels, per
# lane, from the lanes' real contexts and the configuration's dtype.  The
# arithmetic is copied from the program's ``repro.obs.costs`` (0.974-0.996
# of XLA's compiled FLOPs) with two changes that a roofline needs.  A lane
# is priced at the context it really holds, never at the padded ``max_len``
# view the program walks, so a kernel that stops walking padding does not
# change what it is credited with.  And every byte is priced at the
# configuration's itemsize (2 for bfloat16), where the copy defaulted
# activations to float32.  What is counted is the work the algorithm needs:
# weights read once per step, K/V read once per (lane, KV head), each new
# K/V entry written once, one logit row per lane that needs one.
#
# Elementwise pricing, as in repro.obs.costs: one FLOP per arithmetic
# element, transcendentals included.  LayerNorm adds to RMSNorm's six the
# mean it subtracts and the bias it adds.
SOFTMAX_FLOPS_PER_SCORE = 7.0
NORM_FLOPS_PER_ELEM = {"rms_norm": 6.0, "layer_norm": 8.0}
ROPE_FLOPS_PER_ELEM = 4.5


def linear_shapes(d: Dims) -> Sequence[Tuple[int, int, bool]]:
    """``(in, out, bias)`` of one layer's linears."""
    qo, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
    out = [(d.d_model, qo, d.qkv_bias), (d.d_model, kv, d.qkv_bias),
           (d.d_model, kv, d.qkv_bias), (qo, d.d_model, d.out_bias),
           (d.d_model, d.d_ff, d.out_bias), (d.d_ff, d.d_model, d.out_bias)]
    if d.gated:
        out.append((d.d_model, d.d_ff, d.out_bias))
    return out


def _norm_params(d: Dims) -> int:
    return d.d_model * (2 if d.norm == "layer_norm" else 1)


def weight_bytes(d: Dims) -> float:
    """The parameters one step reads whole, once: the layers' linears,
    biases and norms, the final norm and the LM head (the embedding table
    when tied).  An untied embedding table is only gathered from, row by
    row, and is priced with the activations."""
    per_layer = sum(k * n + (n if b else 0) for k, n, b in linear_shapes(d))
    per_layer += 2 * _norm_params(d)
    total = d.n_layers * per_layer + d.vocab * d.d_model + _norm_params(d)
    return float(total * d.itemsize)


def _dense_flops(d: Dims, tokens: int, logit_rows: int) -> float:
    """Linears, norms, RoPE, residuals and the MLP activation for
    ``tokens`` rows, and the LM head for ``logit_rows``."""
    norm = NORM_FLOPS_PER_ELEM[d.norm]
    lin = sum(2.0 * k * n + (n if b else 0) for k, n, b in linear_shapes(d))
    other = (2 * norm * d.d_model
             + ROPE_FLOPS_PER_ELEM * (d.n_heads + d.n_kv_heads) * d.head_dim
             + 2 * d.d_model + (3.0 if d.gated else 4.0) * d.d_ff)
    head = 2.0 * d.d_model * d.vocab + norm * d.d_model
    return d.n_layers * (lin + other) * tokens + head * logit_rows


def _act_bytes(d: Dims, tokens: int) -> float:
    """Activation streams into and out of every linear, the embedding
    gather and the residual/norm passes, at the configuration's itemsize."""
    lin = sum(k + n for k, n, _ in linear_shapes(d))
    per_tok = d.n_layers * (lin + 8 * d.d_model) + 2 * d.d_model
    return float(per_tok * tokens * d.itemsize)


def attended(d: Dims, context: int) -> int:
    """Keys one query at logical length ``context`` attends."""
    return min(context, d.window) if d.window else context


def _kv_entry_bytes(d: Dims) -> int:
    """K and V of one token in one layer."""
    return 2 * d.n_kv_heads * d.head_dim * d.itemsize


def attn_decode(d: Dims, contexts: Iterable[int], page_size: int):
    """``(flops, bytes)`` of the decode-attention kernel over all layers
    for lanes whose real context (keys, the new token's included) is
    given: q.K and p.V over the attended keys, the softmax, K/V read once
    per (lane, KV head), Q read, O written, the block-table row read."""
    flops = nbytes = 0.0
    for c in contexts:
        k = attended(d, c)
        flops += (4.0 * d.head_dim + SOFTMAX_FLOPS_PER_SCORE) * d.n_heads * k
        nbytes += (k * _kv_entry_bytes(d)
                   + 2 * d.n_heads * d.head_dim * d.itemsize
                   + math.ceil(k / page_size) * 4)
    return d.n_layers * flops, d.n_layers * nbytes


def decode_step(d: Dims, contexts: Sequence[int], page_size: int):
    """``(flops, bytes)`` of one whole decode step over the lanes that
    decode in it: every weight read once, the lanes' rows through every
    layer, attention over their real contexts, one K/V entry written per
    lane and layer, one logit row written per lane."""
    n = len(contexts)
    af, ab = attn_decode(d, contexts, page_size)
    flops = _dense_flops(d, n, n) + af
    nbytes = (weight_bytes(d) + _act_bytes(d, n) + ab
              + n * d.n_layers * _kv_entry_bytes(d)
              + n * d.vocab * d.itemsize)
    return flops, nbytes


def _chunk_keys(d: Dims, p0: int, n: int) -> Tuple[int, int]:
    """For a chunk of ``n`` queries at positions ``p0 .. p0+n-1``: the
    scores it computes (each query against the keys it attends) and the
    distinct keys it reads."""
    if d.window:
        scores = sum(attended(d, p + 1) for p in range(p0, p0 + n))
        first = max(0, p0 - d.window + 1)
    else:
        scores = n * p0 + n * (n + 1) // 2
        first = 0
    return scores, p0 + n - first


def attn_prefill(d: Dims, lanes: Iterable[Tuple[int, int]], page_size: int):
    """``(flops, bytes)`` of the prefill-attention kernel over all layers
    for lanes ``(p0, n)``: ``n`` prompt tokens from position ``p0``,
    attending causally (and within the window) over what the lane holds."""
    flops = nbytes = 0.0
    for p0, n in lanes:
        scores, keys = _chunk_keys(d, p0, n)
        flops += ((4.0 * d.head_dim + SOFTMAX_FLOPS_PER_SCORE)
                  * d.n_heads * scores)
        nbytes += (keys * _kv_entry_bytes(d)
                   + 2 * n * d.n_heads * d.head_dim * d.itemsize
                   + math.ceil(keys / page_size) * 4)
    return d.n_layers * flops, d.n_layers * nbytes


def prefill_step(d: Dims, lanes: Sequence[Tuple[int, int]], page_size: int):
    """``(flops, bytes)`` of one whole prefill call over lanes ``(p0, n)``:
    weights once, the real prompt rows through every layer, attention,
    their K/V written once, and one logit row per lane."""
    tokens = sum(n for _, n in lanes)
    af, ab = attn_prefill(d, lanes, page_size)
    flops = _dense_flops(d, tokens, len(lanes)) + af
    nbytes = (weight_bytes(d) + _act_bytes(d, tokens) + ab
              + tokens * d.n_layers * _kv_entry_bytes(d)
              + len(lanes) * d.vocab * d.itemsize)
    return flops, nbytes
