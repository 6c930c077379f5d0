"""Shared test fixtures.

NOTE: no XLA device-count flags here — smoke tests and benches must see the
real single CPU device; only launch/dryrun.py forces 512 host devices.
"""

import dataclasses

import jax
import pytest
from hypothesis import settings

# Property tests replay the same examples on every run (seeded from each
# test function) with no per-example deadline: a tier-1 run must be
# reproducible, and one example that compiles a kernel can take seconds
# on a loaded host.
settings.register_profile("repro", derandomize=True, deadline=None,
                          database=None)
settings.load_profile("repro")


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def reduced_f32(arch: str, **overrides):
    """Reduced same-family config in float32 for CPU numerics."""
    from repro.config import get_reduced

    return dataclasses.replace(get_reduced(arch), dtype="float32", **overrides)


ALL_ARCHS = [
    "gemma3-27b",
    "mistral-large-123b",
    "starcoder2-15b",
    "qwen2.5-3b",
    "llava-next-mistral-7b",
    "mamba2-130m",
    "zamba2-7b",
    "musicgen-medium",
    "llama4-scout-17b-a16e",
    "qwen3-moe-235b-a22b",
]


def make_batch(cfg, rng, batch=2, seq=16):
    """Family-appropriate batch dict (tokens/labels [+ modality stubs])."""
    import jax

    ks = jax.random.split(rng, 3)
    if cfg.family == "audio":
        toks = jax.random.randint(
            ks[0], (batch, seq + 1, cfg.n_codebooks), 0, cfg.vocab_size)
        out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    else:
        toks = jax.random.randint(ks[0], (batch, seq + 1), 0, cfg.vocab_size)
        out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    if cfg.family == "vlm":
        out["img_embeds"] = jax.random.normal(
            ks[1], (batch, cfg.img_tokens, cfg.d_model))
    return out


# Fused-vs-gather logit tolerance on int8 KV pages (reduced f32 models,
# logits of order 1).  Both read paths contract in bf16 with f32
# accumulation, but the fused kernel rounds the probabilities to bf16
# before normalizing and the gather path after: each probability carries
# up to 2^-8 relative rounding either way.  On the reduced configs that
# moves logits by a few 1e-3; 2e-2 leaves a 5x margin while still failing
# on any real masking or scale-folding bug, which moves them by O(0.1).
KV8_LOGIT_TOL = 2e-2


def serve_logged(eng, prompts, forced=None):
    """Serve ``prompts`` on ``eng`` and log, per request, the logits every
    emitted token is sampled from (prefill logits first, then each decode
    step's).  ``forced`` (rid -> token list) makes every lane emit those
    tokens instead of its own greedy pick, so two engines whose read
    paths round differently still see identical inputs at every step and
    their logits stay comparable position by position.  Returns
    ``(outputs, logits)``, both keyed by rid."""
    import numpy as np

    own = eng._sample_next
    logits = {}

    def sample(req):
        logits.setdefault(req.rid, []).append(np.asarray(req.last_logits))
        if forced is None:
            return own(req)
        return forced[req.rid][len(req.output)]

    eng._sample_next = sample
    for p in prompts:
        eng.submit(list(p))
    outputs = {r.rid: list(r.output) for r in eng.run()}
    return outputs, {rid: np.stack(v) for rid, v in logits.items()}
