"""FLOPs and HBM bytes of the serve path's steps and attention kernels, per
lane, from the lanes' real contexts and the configuration's dtype.

The arithmetic is copied from the program's ``repro.obs.costs`` (PR 10:
0.974-0.996 of XLA's compiled FLOPs) with two changes that a roofline
needs.  A lane is priced at the context it really holds, never at the
padded ``max_len`` view the program walks, so a kernel that stops walking
padding does not change what it is credited with.  And every byte is
priced at the configuration's itemsize (2 for bfloat16), where the copy
defaulted activations to float32.  What is counted is the work the
algorithm needs: weights read once per step, K/V read once per
(lane, KV head), each new K/V entry written once, one logit row per lane
that needs one.
"""

from __future__ import annotations

import math
from typing import Iterable, Sequence, Tuple

from bench.yardstick.model import Dims

# elementwise pricing, as in repro.obs.costs (one FLOP per arithmetic
# element, transcendentals included)
SOFTMAX_FLOPS_PER_SCORE = 7.0
RMSNORM_FLOPS_PER_ELEM = 6.0
ROPE_FLOPS_PER_ELEM = 4.5


def linear_shapes(d: Dims) -> Sequence[Tuple[int, int, bool]]:
    """``(in, out, bias)`` of one layer's linears."""
    qo, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
    out = [(d.d_model, qo, d.qkv_bias), (d.d_model, kv, d.qkv_bias),
           (d.d_model, kv, d.qkv_bias), (qo, d.d_model, False),
           (d.d_model, d.d_ff, False), (d.d_ff, d.d_model, False)]
    if d.gated:
        out.append((d.d_model, d.d_ff, False))
    return out


def weight_bytes(d: Dims) -> float:
    """The parameters one step reads whole, once: the layers' linears,
    biases and norm scales, the final norm and the LM head (the embedding
    table when tied).  An untied embedding table is only gathered from,
    row by row, and is priced with the activations."""
    per_layer = sum(k * n + (n if b else 0) for k, n, b in linear_shapes(d))
    per_layer += 2 * d.d_model
    total = d.n_layers * per_layer + d.vocab * d.d_model + d.d_model
    return float(total * d.itemsize)


def _dense_flops(d: Dims, tokens: int, logit_rows: int) -> float:
    """Linears, norms, RoPE, residuals and the MLP activation for
    ``tokens`` rows, and the LM head for ``logit_rows``."""
    lin = sum(2.0 * k * n + (n if b else 0) for k, n, b in linear_shapes(d))
    other = (2 * RMSNORM_FLOPS_PER_ELEM * d.d_model
             + ROPE_FLOPS_PER_ELEM * (d.n_heads + d.n_kv_heads) * d.head_dim
             + 2 * d.d_model + (3.0 if d.gated else 4.0) * d.d_ff)
    head = 2.0 * d.d_model * d.vocab + RMSNORM_FLOPS_PER_ELEM * d.d_model
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


# ----------------------------------------------------------------- decode
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


# ---------------------------------------------------------------- prefill
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


def least_time(flops: float, nbytes: float, peak: dict, chips: int):
    """The least seconds ``chips`` chips need for the work, and which
    resource binds (``"flops"`` or ``"hbm"``)."""
    tf = flops / (chips * peak["flops"])
    tb = nbytes / (chips * peak["hbm_bw"])
    return (tf, "flops") if tf >= tb else (tb, "hbm")
