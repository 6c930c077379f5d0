"""Plain float32 reference of the dense GQA decoder both configurations
run (Qwen2 and Starcoder2 as the configuration files state them), and the
comparison that decides ``correct``.

It follows the published equations: RMSNorm, rotary position embedding
on the two halves of each head (``rotate_half``), grouped-query attention
with an optional sliding window, SwiGLU or a tanh-approximated GELU MLP,
a tied or untied LM head.  No kernel, no cache, no batching of lanes:
whole sequences, packed end to end and kept apart by a segment mask,
computed one layer at a time with ``Precision.HIGHEST`` so that float32
stays float32 on a TPU.  It imports nothing of the program; its weights
are made again from the seed by :mod:`bench.yardstick.weights`, rounded to
the served type and widened to float32.

What is compared: for every served token of a sample of the requests a
run served (finished or still running), how far the token's reference logit lies below the reference's
best logit at that position.  Greedy decoding in exact arithmetic gives 0;
rounding in the served precision gives small gaps at near-ties; a wrong
page, position, mask or weight gives gaps of the order of the logits.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.yardstick import weights
from bench.yardstick.model import Dims

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, pos, theta):
    """x: (T, H, Dh); rotate the two halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _linear(p, x):
    y = _mm(x, p["w"])
    return y + p["bias"] if "bias" in p else y


def _block(d: Dims, q_block: int, x, lp, pos, seg):
    """One decoder layer over the packed rows ``x`` (T, d)."""
    t = x.shape[0]
    g = d.n_heads // d.n_kv_heads
    h = _rms(x, 1.0 + lp["ln1"], d.norm_eps)
    q = _linear(lp["attn"]["wq"], h).reshape(t, d.n_heads, d.head_dim)
    k = _linear(lp["attn"]["wk"], h).reshape(t, d.n_kv_heads, d.head_dim)
    v = _linear(lp["attn"]["wv"], h).reshape(t, d.n_kv_heads, d.head_dim)
    q, k = _rope(q, pos, d.rope_theta), _rope(k, pos, d.rope_theta)
    nb = t // q_block
    qb = q.reshape(nb, q_block, d.n_kv_heads, g, d.head_dim)

    def attend(args):
        qi, pq, sq = args                   # (Bq, Hkv, G, Dh), (Bq,), (Bq,)
        s = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HI)
        s = s * d.head_dim ** -0.5
        ok = (sq[:, None] == seg[None, :]) & (pos[None, :] <= pq[:, None])
        if d.window:
            ok &= pos[None, :] > pq[:, None] - d.window
        s = jnp.where(ok[None, None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    o = jax.lax.map(attend, (qb, pos.reshape(nb, q_block),
                             seg.reshape(nb, q_block)))
    x = x + _mm(o.reshape(t, d.n_heads * d.head_dim), lp["attn"]["wo"]["w"])
    h = _rms(x, 1.0 + lp["ln2"], d.norm_eps)
    m = lp["mlp"]
    if d.gated:
        a = jax.nn.silu(_mm(h, m["w_gate"]["w"])) * _mm(h, m["w_up"]["w"])
    else:
        a = _gelu_tanh(_mm(h, m["w_up"]["w"]))
    return x + _mm(a, m["w_down"]["w"])


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run_layer(d: Dims, q_block: int, x, key, index, pos, seg):
    lp = _f32(weights.layer(d, key, index))
    return _block(d, q_block, x, lp, pos, seg)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(d: Dims, key, tokens):
    return _f32(weights.top(d, key))["embed"][tokens]


@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(d: Dims, key, x, rows, targets):
    """Per served row: reference best logit minus the served token's."""
    top = _f32(weights.top(d, key))
    h = _rms(x[rows], 1.0 + top["final_norm"], d.norm_eps)
    head = top["embed"].T if d.tied else top["lm_head"]["w"]
    logits = _mm(h, head)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return best - got


def pack(seqs: Sequence[Tuple[Sequence[int], Sequence[int]]], tokens: int,
         rows: int, targets: Optional[Sequence[Sequence[int]]] = None):
    """Pack ``(prompt, served)`` pairs end to end into ``tokens`` slots.

    Row ``j`` of a sequence's served tokens is predicted at position
    ``len(prompt) - 1 + j``, so each sequence contributes its prompt and
    all but its last served token.  The row's target is the served token,
    or ``targets[i][j]`` where given (another token read at the same
    position).  Returns the packed arrays and the number of real rows;
    raises if the sample does not fit."""
    tok = np.zeros(tokens, np.int32)
    pos = np.zeros(tokens, np.int32)
    seg = np.zeros(tokens, np.int32)
    row = np.zeros(rows, np.int32)
    tgt = np.zeros(rows, np.int32)
    at = nr = 0
    for i, (prompt, served) in enumerate(seqs, start=1):
        full = list(prompt) + list(served[:-1])
        n = len(full)
        if at + n > tokens or nr + len(served) > rows:
            raise ValueError("sample does not fit the reference's budget")
        tok[at:at + n] = full
        pos[at:at + n] = np.arange(n)
        seg[at:at + n] = i
        tg = served if targets is None else targets[i - 1]
        if len(tg) != len(served):
            raise ValueError("one target per served token")
        for j, t in enumerate(tg):
            row[nr + j] = at + len(prompt) - 1 + j
            tgt[nr + j] = t
        at += n
        nr += len(served)
    return tok, pos, seg, row, tgt, nr


def fits(seqs, tokens: int, rows: int) -> bool:
    need = sum(len(p) + len(s) - 1 for p, s in seqs)
    return need <= tokens and sum(len(s) for _, s in seqs) <= rows


def served_gaps(d: Dims, seed: int, seqs, *, tokens: int, rows: int,
                q_block: int, targets=None) -> np.ndarray:
    """The gap of every served token of ``seqs`` (``(prompt, served)``
    pairs), in order; with ``targets``, the gap of those tokens at the
    same positions (see :func:`pack`)."""
    key = weights.seed_key(seed)
    tok, pos, seg, row, tgt, n = pack(seqs, tokens, rows, targets)
    pos_d, seg_d = jnp.asarray(pos), jnp.asarray(seg)
    x = _embed(d, key, jnp.asarray(tok))
    for i in range(d.n_layers):
        x = _run_layer(d, q_block, x, key, jnp.int32(i), pos_d, seg_d)
    gaps = _gaps(d, key, x, jnp.asarray(row), jnp.asarray(tgt))
    return np.asarray(gaps)[:n]


def sample(served: List[Tuple[Sequence[int], Sequence[int]]], seed: int,
           tokens: int, rows: int):
    """The requests to compare: the longest (prompt and served tokens),
    then others in an order drawn from the seed, as many as the budget
    holds."""
    if not served:
        return []
    order = sorted(range(len(served)),
                   key=lambda i: -(len(served[i][0]) + len(served[i][1])))
    chosen = [order[0]]
    rest = np.random.default_rng(seed).permutation(order[1:]).tolist()
    for i in rest:
        if fits([served[j] for j in chosen + [i]], tokens, rows):
            chosen.append(i)
    return [served[i] for i in chosen]
