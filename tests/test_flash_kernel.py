"""Flash-attention Pallas kernel vs oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.models.attention import attend_dense


def _qkv(b, s, hq, hkv, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, hq, d)),
            jax.random.normal(ks[1], (b, s, hkv, d)),
            jax.random.normal(ks[2], (b, s, hkv, d)))


@pytest.mark.parametrize("window", [0, 64])
@pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128)])
def test_flash_kernel_vs_dense(window, bq, bk):
    q, k, v = _qkv(2, 256, 8, 4, 32)
    out = flash_attention(q, k, v, window=window, block_q=bq, block_kv=bk,
                          interpret=True)
    pos = jnp.broadcast_to(jnp.arange(256), (2, 256))
    ref = attend_dense(q, k, v, pos, pos, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_kernel_padding_path():
    q, k, v = _qkv(1, 200, 4, 4, 16, seed=3)
    out = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    assert out.shape == q.shape
    pos = jnp.broadcast_to(jnp.arange(200), (1, 200))
    ref = attend_dense(q, k, v, pos, pos, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@given(
    b=st.integers(1, 2),
    s_blocks=st.integers(1, 3),
    hkv=st.sampled_from([1, 2, 4]),
    group=st.sampled_from([1, 2, 4]),
    d=st.sampled_from([16, 64]),
    window=st.sampled_from([0, 32]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_flash_kernel_property(b, s_blocks, hkv, group, d, window, seed):
    s = 64 * s_blocks
    q, k, v = _qkv(b, s, hkv * group, hkv, d, seed=seed)
    out = flash_attention(q, k, v, window=window, block_q=64, block_kv=64,
                          interpret=True)
    ref = flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), window=window).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
