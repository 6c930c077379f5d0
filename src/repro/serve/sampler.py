"""Token sampling: greedy / temperature / top-k, one id per lane.

:func:`sample` is the sampler the jitted paged dispatches call on the
logits they just computed, so a step hands the host ``(B,)`` ids rather
than ``(B, V)`` logits.  ``temperature`` and ``top_k`` are Python values
(static under ``jit``: the trace adapts to them); ``key`` is one key per
call, split per lane inside, so each lane draws from its own stream.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sample(logits: jnp.ndarray, key: jax.Array, temperature: float = 0.0,
           top_k: int = 0) -> jnp.ndarray:
    """logits: (B, V) -> (B,) int32.  Greedy (``argmax``) when
    ``temperature <= 0``; otherwise a categorical draw over
    ``logits / temperature``, restricted to each lane's ``top_k`` largest
    when ``top_k > 0``."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    keys = jax.random.split(key, logits.shape[0])
    draw = jax.vmap(lambda k, row: jax.random.categorical(k, row))
    return draw(keys, logits).astype(jnp.int32)


def sample_rows(logits: jnp.ndarray, key: jax.Array,
                temperature: float = 0.0, top_k: int = 0):
    """A dispatch's last-position logits ``(B, S, V)`` (``(B, S, K, V)``
    for audio, which samples codebook 0: the engine's token stream
    carries one id broadcast across codebooks) -> ``(ids (B,) int32,
    finite (B,) bool)``, ``finite`` false where any of the lane's logits
    is NaN or infinite."""
    last = logits[:, -1]
    finite = jnp.all(jnp.isfinite(last).reshape(last.shape[0], -1), axis=-1)
    rows = last[:, 0] if last.ndim == 3 else last
    return sample(rows, key, temperature, top_k), finite
