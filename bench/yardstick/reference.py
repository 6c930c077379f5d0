"""What every architecture's float32 reference shares: the published
equations' common pieces, the packing of served sequences, the layer by
layer walk, and the choice of requests to compare.

An architecture module of ``bench/yardstick/arch`` writes its block from
these and runs it through :func:`gaps_by_layer`.  No kernel, no cache, no
batching of lanes: whole sequences, packed end to end and kept apart by a
segment mask, computed one layer at a time with ``Precision.HIGHEST`` so
that float32 stays float32 on a TPU.  Nothing here imports the program;
the weights are made again from the seed, rounded to the served type and
widened to float32.

What is compared: for every served token of a sample of the requests a
run served (finished or still running), how far the token's reference
logit lies below the reference's best logit at that position.  Greedy
decoding in exact arithmetic gives 0; rounding in the served precision
gives small gaps at near-ties; a wrong page, position, mask or weight
gives gaps of the order of the logits.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def layer_norm(x, w, b, eps):
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    return c * jax.lax.rsqrt(var + eps) * w + b


def rope(x, pos, theta):
    """x: (T, H, Dh); rotate the two halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


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


def gaps_by_layer(seqs, n_layers: int, embed: Callable, layer: Callable,
                  head: Callable, *, tokens: int, rows: int,
                  targets=None) -> np.ndarray:
    """The gap of every served token of ``seqs`` (``(prompt, served)``
    pairs), in order; with ``targets``, the gap of those tokens at the same
    positions (see :func:`pack`).  ``embed(tok)`` gives the packed rows,
    ``layer(x, index, pos, seg)`` runs one layer over them, and
    ``head(x, rows, targets)`` gives each row's best logit minus its
    target's."""
    tok, pos, seg, row, tgt, n = pack(seqs, tokens, rows, targets)
    pos_d, seg_d = jnp.asarray(pos), jnp.asarray(seg)
    x = embed(jnp.asarray(tok))
    for i in range(n_layers):
        x = layer(x, jnp.int32(i), pos_d, seg_d)
    gaps = head(x, jnp.asarray(row), jnp.asarray(tgt))
    return np.asarray(gaps)[:n]


def fits(seqs, tokens: int, rows: int) -> bool:
    need = sum(len(p) + len(s) - 1 for p, s in seqs)
    return need <= tokens and sum(len(s) for _, s in seqs) <= rows


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
