"""Optimizers, schedules, gradient compression, data pipeline."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.config.base import ModelConfig, TrainConfig
from repro.data import DataPipeline
from repro.optim import (
    adamw_init,
    adamw_update,
    adafactor_init,
    adafactor_update,
    compress_decompress,
    cosine_warmup,
    ef_state_init,
    error_feedback_compress,
    make_optimizer,
    sgd_init,
    sgd_update,
)

from conftest import reduced_f32


def _quad_problem(seed=0):
    """min ||w - target||^2 — any sane optimizer converges."""
    k = jax.random.PRNGKey(seed)
    target = jax.random.normal(k, (8, 8))
    params = {"w": jnp.zeros((8, 8))}

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    return params, loss, target


@pytest.mark.parametrize("name", ["adamw", "adafactor", "sgd"])
def test_optimizer_converges(name):
    params, loss, target = _quad_problem()
    init_fn, update_fn = make_optimizer(name)
    state = init_fn(params)
    tcfg = TrainConfig(weight_decay=0.0, beta1=0.9 if name != "sgd" else 0.0)
    lr = jnp.asarray(0.1)
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state = update_fn(g, state, params, tcfg, lr)
    assert float(loss(params)) < 1e-2
    assert int(state.step) == 200


def test_adamw_matches_reference_numpy():
    """First two AdamW steps vs a hand-rolled numpy implementation."""
    params = {"w": jnp.asarray([[1.0, -2.0]])}
    g = {"w": jnp.asarray([[0.5, 0.25]])}
    tcfg = TrainConfig(weight_decay=0.01, beta1=0.9, beta2=0.95, eps=1e-8)
    state = adamw_init(params)
    lr = jnp.asarray(0.1)
    p1, state = adamw_update(g, state, params, tcfg, lr)

    w = np.array([[1.0, -2.0]])
    gn = np.array([[0.5, 0.25]])
    m = 0.1 * gn
    v = 0.05 * gn * gn
    mh, vh = m / 0.1, v / 0.05
    w1 = w - 0.1 * (mh / (np.sqrt(vh) + 1e-8) + 0.01 * w)
    np.testing.assert_allclose(np.asarray(p1["w"]), w1, rtol=1e-5)


def test_adamw_bf16_params_fp32_state():
    params = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    state = adamw_init(params)
    assert state.inner["m"]["w"].dtype == jnp.float32
    g = {"w": jnp.full((4, 4), 0.1, jnp.bfloat16)}
    p1, s1 = adamw_update(g, state, params, TrainConfig(), jnp.asarray(1e-2))
    assert p1["w"].dtype == jnp.bfloat16


def test_cosine_warmup_schedule():
    lr0 = float(cosine_warmup(0, 1.0, warmup=10, total=100))
    lr_w = float(cosine_warmup(10, 1.0, warmup=10, total=100))
    lr_end = float(cosine_warmup(100, 1.0, warmup=10, total=100))
    assert lr0 < 0.11
    assert abs(lr_w - 1.0) < 1e-6
    assert abs(lr_end - 0.1) < 1e-3  # min_frac floor
    # monotone decay after warmup
    lrs = [float(cosine_warmup(s, 1.0, 10, 100)) for s in range(10, 100, 10)]
    assert all(a >= b for a, b in zip(lrs, lrs[1:]))


@given(bits=st.sampled_from([4, 8]), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_compress_decompress_bounded_error(bits, seed):
    g = jax.random.normal(jax.random.PRNGKey(seed), (64,))
    out = compress_decompress(g, bits)
    qmax = 2 ** (bits - 1) - 1
    scale = float(jnp.max(jnp.abs(g))) / qmax
    assert float(jnp.max(jnp.abs(out - g))) <= scale / 2 + 1e-6


def test_error_feedback_unbiased_accumulation():
    """sent + ef' == grads + ef (no information lost across steps)."""
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (32,))}
    ef = ef_state_init(g)
    sent, ef2 = error_feedback_compress(g, ef, bits=8)
    np.testing.assert_allclose(
        np.asarray(sent["w"] + ef2["w"]), np.asarray(g["w"]), rtol=1e-5,
        atol=1e-6)


def test_error_feedback_convergence():
    """EF-compressed SGD still converges on the quadratic."""
    params, loss, target = _quad_problem(3)
    state = sgd_init(params)
    ef = ef_state_init(params)
    tcfg = TrainConfig(beta1=0.0, weight_decay=0.0)
    for _ in range(300):
        g = jax.grad(loss)(params)
        g, ef = error_feedback_compress(g, ef, bits=4)
        params, state = sgd_update(g, state, params, tcfg, jnp.asarray(0.05))
    assert float(loss(params)) < 1e-2


class TestDataPipeline:
    def _cfg(self):
        return reduced_f32("qwen2.5-3b")

    def test_determinism_and_restart(self):
        cfg = self._cfg()
        p1 = DataPipeline(cfg, batch=4, seq_len=16, seed=5)
        b0 = p1.batch_at(0)
        b1 = p1.batch_at(1)
        # a fresh pipeline resumed at step 1 yields the identical batch
        p2 = DataPipeline(cfg, batch=4, seq_len=16, seed=5)
        np.testing.assert_array_equal(p2.batch_at(1)["tokens"], b1["tokens"])
        assert not np.array_equal(b0["tokens"], b1["tokens"])

    def test_labels_shifted(self):
        cfg = self._cfg()
        p = DataPipeline(cfg, batch=2, seq_len=8, seed=0)
        b = p.batch_at(0)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_host_sharding_disjoint(self):
        cfg = self._cfg()
        batches = [
            DataPipeline(cfg, batch=8, seq_len=16, seed=1,
                         host_id=h, n_hosts=2).batch_at(0)["tokens"]
            for h in (0, 1)
        ]
        assert batches[0].shape == (4, 16)
        assert not np.array_equal(batches[0], batches[1])

    def test_vlm_audio_batches(self):
        vlm = reduced_f32("llava-next-mistral-7b")
        b = DataPipeline(vlm, batch=2, seq_len=8).batch_at(0)
        assert b["img_embeds"].shape == (2, vlm.img_tokens, vlm.d_model)
        audio = reduced_f32("musicgen-medium")
        b = DataPipeline(audio, batch=2, seq_len=8).batch_at(0)
        assert b["tokens"].shape == (2, 8, audio.n_codebooks)

    def test_prefetch_thread(self):
        cfg = self._cfg()
        p = DataPipeline(cfg, batch=2, seq_len=8, prefetch=2)
        p.start_prefetch()
        b = p.get_prefetched()
        assert b["tokens"].shape == (2, 8)
        p.stop()


class TestPrefetchRobustness:
    def _cfg(self):
        return reduced_f32("qwen2.5-3b")

    def test_full_queue_never_drops_a_batch(self):
        """Slow consumer, prefetch=1: the worker hits queue.Full
        constantly.  Every batch must still arrive exactly once, in
        order — the old code regenerated (and so skipped) a batch on
        every Full."""
        import time

        cfg = self._cfg()
        p = DataPipeline(cfg, batch=2, seq_len=8, seed=3, prefetch=1)
        expected = [DataPipeline(cfg, batch=2, seq_len=8,
                                 seed=3).batch_at(i) for i in range(6)]
        p.start_prefetch()
        time.sleep(0.4)  # let the worker slam into Full repeatedly
        try:
            for i in range(6):
                got = p.get_prefetched()
                np.testing.assert_array_equal(
                    got["tokens"], expected[i]["tokens"]), i
                time.sleep(0.05)
        finally:
            p.stop()

    def test_worker_exception_propagates(self):
        """A worker that dies must surface its exception through
        get_prefetched, not present as an eternal queue.Empty."""
        cfg = self._cfg()
        p = DataPipeline(cfg, batch=2, seq_len=8, prefetch=2)
        p.batch_at = lambda step: (_ for _ in ()).throw(
            OSError("disk gone"))
        p.start_prefetch()
        with pytest.raises(RuntimeError, match="prefetch worker") as ei:
            p.get_prefetched(timeout=5.0)
        assert isinstance(ei.value.__cause__, OSError)
        p.stop()
