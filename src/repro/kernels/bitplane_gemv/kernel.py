"""Bit-plane GEMV Pallas kernel — the IMAGine engine's TPU hot path.

Mapping from the paper (Fig. 2) to the TPU memory hierarchy:

  GEMV tile (12x2 PIM blocks)   -> one grid cell: a (block_k x block_n)
                                   weight tile resident in VMEM
  BRAM-stationary weights        -> packed int8 words streamed HBM->VMEM
                                   exactly once (b/8 bytes per weight)
  bit-serial PE pass (radix-2)   -> one plane-digit extraction + MXU matmul;
                                   ``radix`` bits retire per pass (radix=2
                                   reproduces the paper's slice4 variant)
  east->west accumulation        -> the minor grid dimension walks K tiles,
                                   accumulating into the same VMEM out block
  column shift-register readout  -> the final out-block writeback

Grid: ``(B_blocks, N_blocks, K_blocks)`` with K minor so the output block
stays VMEM-resident across the whole east->west sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(packed_ref, scale_ref, x_ref, o_ref, *, bits: int, radix: int,
            n_k_blocks: int, block_k: int):
    """One (batch, n, k) grid cell.

    packed_ref : (block_k * bits // 8, block_n) int8   — packed weight tile
    scale_ref  : (1, block_n) f32                      — per-channel scales
    x_ref      : (block_b, block_k) f32/bf16           — activation slice
    o_ref      : (block_b, block_n) f32                — accumulator block
    """
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    per_byte = 8 // bits
    # widen to 32-bit lanes before any shift: the TPU's vector unit has no
    # 8-bit shifts, so sub-byte unpacking must happen on int32 words
    words = packed_ref[...].astype(jnp.int32) & 0xFF  # (block_k/per_byte, N)

    if per_byte > 1:
        # unpack the packed K axis in-register (VREG shift/mask), restoring
        # K-major order: element k = i*per_byte + s lives in word i, digit s.
        mask = (1 << bits) - 1
        digs = [(words >> (s * bits)) & mask for s in range(per_byte)]
        stacked = jnp.stack(digs, axis=1)  # (words_k, per_byte, block_n)
        code_i32 = stacked.reshape(block_k, words.shape[-1])
    else:
        code_i32 = words  # (block_k, block_n) two's-complement codes

    x = x_ref[...].astype(jnp.float32)
    acc = jnp.zeros((x.shape[0], words.shape[-1]), jnp.float32)

    # --- the bit-serial east->west passes (static unroll over digits) ------
    n_digits = bits // radix
    digit_mask = (1 << radix) - 1
    for d in range(n_digits):
        digit = (code_i32 >> (d * radix)) & digit_mask
        if d == n_digits - 1:
            # top digit carries the two's-complement sign
            sign = (digit >> (radix - 1)) & 1
            digit = digit - (sign << radix)
        partial = jax.lax.dot_general(
            x,
            digit.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = acc + float(1 << (d * radix)) * partial

    o_ref[...] += acc

    @pl.when(k_idx == n_k_blocks - 1)
    def _finalize():
        o_ref[...] *= scale_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("bits", "radix", "block_b", "block_n", "block_k",
                     "interpret", "out_dtype"),
)
def bitplane_gemv_pallas(
    packed: jnp.ndarray,   # (K * bits // 8, N) int8
    scale: jnp.ndarray,    # (1, N) f32
    x: jnp.ndarray,        # (B, K)
    *,
    bits: int = 8,
    radix: int = 1,
    block_b: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    b, k = x.shape
    kp, n = packed.shape
    per_byte = 8 // bits
    assert kp * per_byte == k, (kp, per_byte, k)
    assert bits % radix == 0, (bits, radix)

    block_b = min(block_b, b)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert b % block_b == 0 and n % block_n == 0 and k % block_k == 0, (
        "caller (ops.py) must pad to block multiples"
    )
    assert block_k % per_byte == 0
    grid = (b // block_b, n // block_n, k // block_k)

    return pl.pallas_call(
        functools.partial(
            _kernel,
            bits=bits,
            radix=radix,
            n_k_blocks=grid[2],
            block_k=block_k,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (block_k // per_byte, block_n), lambda bb, j, kk: (kk, j)
            ),
            pl.BlockSpec((1, block_n), lambda bb, j, kk: (0, j)),
            pl.BlockSpec((block_b, block_k), lambda bb, j, kk: (bb, kk)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda bb, j, kk: (bb, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=interpret,
        name="bitplane_gemv",
    )(packed, scale, x).astype(out_dtype)
