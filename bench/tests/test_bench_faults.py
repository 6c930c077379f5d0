"""A run with the timed path broken underneath must come out not
correct.  Each test skips the harness's look for a chip, drives the rest
of a run on the CPU at tiny widths, and plants one fault a serving cell
can have:

* a token altered where it is produced (the sampler);
* a step that returns its state unchanged (decode writes no K/V);
* the exchange between chips left out (each row-parallel linear keeps
  only one chip's partial sum, as a missing all-reduce would leave it).
"""

import jax
import jax.numpy as jnp

import _tiny
from bench import harness


def _assert_caught(out):
    gap = out["checks"]["mean_gap"]
    assert gap["value"] > gap["limit"], out["checks"]
    assert not out["correct"]


def test_sound_run_is_correct(monkeypatch):
    out = _tiny.run(_tiny.cell(), monkeypatch)
    assert out["correct"], out["checks"]


def test_altered_token_is_caught(monkeypatch):
    from repro.serve.engine import ServeEngine

    orig = ServeEngine._sample_next
    count = [0]

    def altered(self, req):
        tok = orig(self, req)
        count[0] += 1
        return (tok + 1) % self.cfg.vocab_size if count[0] % 3 == 0 else tok

    monkeypatch.setattr(ServeEngine, "_sample_next", altered)
    _assert_caught(_tiny.run(_tiny.cell(), monkeypatch))


def test_unchanged_state_is_caught(monkeypatch):
    build = harness.build_engine

    def frozen(*a, **k):
        eng = build(*a, **k)
        dec = eng._decode_paged

        def step(params, pages, *rest):
            before = jax.tree.map(jnp.copy, pages)  # the input is donated
            logits, _ = dec(params, pages, *rest)
            return logits, before

        eng._decode_paged = step
        return eng

    monkeypatch.setattr(harness, "build_engine", frozen)
    _assert_caught(_tiny.run(_tiny.cell(), monkeypatch))


def test_missing_exchange_is_caught(monkeypatch):
    from repro.models import layers, transformer

    c = _tiny.cell("starcoder2-15b-tp4", norm=_tiny.PROGRAM_NORM)
    d = c.dims
    row_in = {d.n_heads * d.head_dim, d.d_ff}
    dense = layers.dense

    def one_shard(p, x, eng=None):
        w = p["w"] if isinstance(p, dict) else p
        if w.shape[0] in row_in and w.shape[1] == d.d_model:
            k = w.shape[0] // 4
            return jnp.matmul(x[..., :k], w[:k].astype(x.dtype))
        return dense(p, x, eng)

    monkeypatch.setattr(layers, "dense", one_shard)
    monkeypatch.setattr(transformer, "dense", one_shard)
    _assert_caught(_tiny.run(c, monkeypatch))
