"""Pallas kernel validation: shape/dtype/bits/radix sweeps against the
pure-jnp oracles (interpret mode), plus hypothesis property checks."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.gemv_engine import (
    gemv_bit_serial_reference,
    gemv_reference,
    quantize_linear,
)
from repro.kernels.bitplane_gemv.ops import bitplane_gemv
from repro.kernels.bitplane_gemv.ref import bitplane_gemv_ref
from repro.kernels.int8_matvec.ops import int8_matvec
from repro.kernels.int8_matvec.ref import int8_matvec_ref

SHAPES = [(1, 64, 48), (3, 300, 130), (8, 1024, 512), (128, 256, 128)]
BITS_RADIX = [(8, 1), (8, 2), (8, 4), (4, 1), (4, 2), (2, 1), (2, 2)]


def _data(b, k, n, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((k, n)).astype(dtype))
    x = jnp.asarray(rng.standard_normal((b, k)).astype(dtype))
    return w, x


@pytest.mark.parametrize("b,k,n", SHAPES)
@pytest.mark.parametrize("bits,radix", BITS_RADIX)
def test_bitplane_kernel_vs_ref(b, k, n, bits, radix):
    w, x = _data(b, k, n)
    ql = quantize_linear(w, bits)
    y_k = bitplane_gemv(ql.packed, ql.scale, x, bits=bits, radix=radix,
                        interpret=True)
    y_r = bitplane_gemv_ref(ql.packed, ql.scale, x, bits=bits, radix=radix)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_bitplane_kernel_dtypes(dtype):
    w, x = _data(4, 256, 128)
    x = x.astype(dtype)
    ql = quantize_linear(w, 8)
    y_k = bitplane_gemv(ql.packed, ql.scale, x, bits=8, radix=1,
                        interpret=True, out_dtype=jnp.float32)
    y_r = bitplane_gemv_ref(ql.packed, ql.scale, x.astype(jnp.float32),
                            bits=8, radix=1)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-2, atol=2e-1)


def test_bitplane_kernel_1d_input():
    w, _ = _data(1, 128, 64)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(128,).astype(np.float32))
    ql = quantize_linear(w, 8)
    y = bitplane_gemv(ql.packed, ql.scale, x, bits=8, interpret=True)
    assert y.shape == (64,)


def test_radix_variants_agree():
    """radix-2 (paper baseline), radix-4 ("slice4") and nibble passes are
    numerically identical — the paper's latency knob, not a numerics knob."""
    w, x = _data(2, 512, 64, seed=3)
    ql = quantize_linear(w, 8)
    outs = [
        bitplane_gemv(ql.packed, ql.scale, x, bits=8, radix=r, interpret=True)
        for r in (1, 2, 4)
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("b,k,n", SHAPES[:3])
def test_int8_matvec_vs_ref(b, k, n):
    w, x = _data(b, k, n, seed=7)
    ql = quantize_linear(w, 8)
    y_k = int8_matvec(ql.packed, ql.scale, x, interpret=True)
    y_r = int8_matvec_ref(ql.packed, ql.scale, x)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-5, atol=1e-4)


def test_bitparallel_equals_bitserial():
    """int8 bit-parallel baseline == bit-serial engine on 8-bit weights."""
    w, x = _data(4, 192, 96, seed=11)
    ql = quantize_linear(w, 8)
    y_bp = int8_matvec(ql.packed, ql.scale, x, interpret=True)
    y_bs = bitplane_gemv(ql.packed, ql.scale, x, bits=8, radix=1,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(y_bp), np.asarray(y_bs),
                               rtol=1e-5, atol=1e-4)


@given(
    b=st.integers(1, 8),
    k=st.integers(8, 96),
    n=st.integers(1, 48),
    bits=st.sampled_from([2, 4, 8]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=15, deadline=None)
def test_bitplane_kernel_property(b, k, n, bits, seed):
    k = k * (8 // bits)
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((b, k)).astype(np.float32))
    ql = quantize_linear(w, bits)
    y_k = bitplane_gemv(ql.packed, ql.scale, x, bits=bits, interpret=True)
    y_ref = gemv_reference(ql, x)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)


def test_engine_reference_vs_bit_serial_oracle():
    w, x = _data(3, 128, 64, seed=13)
    for bits in (2, 4, 8):
        ql = quantize_linear(w, bits)
        y0 = gemv_reference(ql, x)
        for radix in (r for r in (1, 2, 4) if bits % r == 0):
            y1 = gemv_bit_serial_reference(ql, x, radix=radix)
            np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                       rtol=1e-5, atol=1e-4)
