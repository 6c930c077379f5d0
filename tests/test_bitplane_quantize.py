"""Property tests: quantization + bit-plane packing invariants."""

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.bitplane import (
    from_bitplanes,
    pack_weights,
    to_bitplanes,
    unpack_weights,
)
from repro.core.quantize import dequantize, quantize_symmetric


@given(
    bits=st.sampled_from([2, 4, 8]),
    k=st.integers(1, 16),
    n=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=60, deadline=None)
def test_pack_unpack_roundtrip(bits, k, n, seed):
    per_byte = 8 // bits
    k = k * per_byte  # packing axis must divide
    rng = np.random.default_rng(seed)
    qmax = 2 ** (bits - 1) - 1
    q = rng.integers(-qmax, qmax + 1, size=(k, n)).astype(np.int8)
    packed = pack_weights(jnp.asarray(q), bits, axis=0)
    assert packed.shape == (k // per_byte, n)
    back = unpack_weights(packed, bits, axis=0)
    np.testing.assert_array_equal(np.asarray(back), q)


@given(
    bits=st.sampled_from([2, 4, 8]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=30, deadline=None)
def test_bitplane_reassembly(bits, seed):
    rng = np.random.default_rng(seed)
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1)
    q = rng.integers(lo, hi, size=(5, 7))
    planes = to_bitplanes(q, bits)
    assert planes.shape == (bits, 5, 7)
    assert set(np.unique(planes)) <= {0, 1}
    np.testing.assert_array_equal(from_bitplanes(planes, bits), q)


@given(
    bits=st.sampled_from([2, 4, 8]),
    seed=st.integers(0, 2**31 - 1),
    scale_pow=st.integers(-3, 3),
)
@settings(max_examples=40, deadline=None)
def test_quantize_error_bound(bits, seed, scale_pow):
    """|w - deq(q)| <= scale/2 elementwise (symmetric round-to-nearest)."""
    rng = np.random.default_rng(seed)
    w = (rng.standard_normal((32, 8)) * 10.0 ** scale_pow).astype(np.float32)
    q, scale = quantize_symmetric(jnp.asarray(w), bits, axis=0)
    deq = np.asarray(dequantize(q, scale))
    err = np.abs(w - deq)
    bound = np.broadcast_to(np.asarray(scale) / 2, w.shape) + 1e-7
    assert np.all(err <= bound)


def test_quantize_preserves_sign_and_zero():
    w = jnp.asarray([[0.0, -1.0, 1.0, -0.5]]).T
    q, scale = quantize_symmetric(w, 8, axis=0)
    q = np.asarray(q)
    assert q[0, 0] == 0
    assert q[1, 0] < 0 and q[2, 0] > 0
    assert q[1, 0] == -q[2, 0]


def test_quantize_zero_matrix():
    q, scale = quantize_symmetric(jnp.zeros((4, 4)), 8, axis=0)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.isfinite(np.asarray(scale)))
