"""The main-path kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: block shapes off the (8, 128) tiling, vector ops Mosaic
does not lower, too much VMEM.  These tests compile each kernel for a
described ``v5e:2x2`` topology — no chip needed, the compiler ships with
jaxlib — at the widths the serving path runs:

* fused paged attention, decode and chunked prefill, bf16 and int8 pools,
  at qwen2.5-3b's heads (2 KV heads, 8 queries each) and at the per-shard
  heads of starcoder2-15b on four chips (1 KV head, 12 queries);
* the bit-plane GEMV at bits 8 and 4 for a 2048→11008 up projection and
  its 11008→2048 down projection.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and collecting workers must all see the
same tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitplane_gemv.ops import bitplane_gemv
from repro.kernels.paged_attention.kernel import (
    paged_attention_pallas,
    paged_prefill_pallas,
)

PAGE, DH, LANES, MAX_LEN = 16, 128, 8, 4096
N_BLOCKS = MAX_LEN // PAGE
N_PAGES = LANES * N_BLOCKS + 1

HEADS = {"qwen2.5-3b": (2, 8), "starcoder2-15b/4": (1, 12)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("kv_bits", [0, 8])
@pytest.mark.parametrize("model", sorted(HEADS))
def test_paged_attention_compiles(one_chip, model, kv_bits, step):
    hkv, group = HEADS[model]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((hkv, N_PAGES, PAGE, DH), jnp.int8 if kv_bits else
               jnp.bfloat16)
    scales = (sds((hkv, N_PAGES, PAGE), jnp.bfloat16),) * 2 if kv_bits \
        else ()
    tables = sds((LANES, N_BLOCKS), jnp.int32)
    lane_pos = sds((LANES,), jnp.int32)
    window = sds((1,), jnp.int32)
    if step == "decode":
        q = sds((LANES, hkv, group, DH), jnp.bfloat16)
        text = _compile(
            lambda *a: paged_attention_pallas(*a, interpret=False),
            q, pool, pool, tables, lane_pos, window, *scales)
        name = "paged_attention_decode"
    else:
        chunk = 32
        q = sds((LANES, hkv, chunk, group, DH), jnp.bfloat16)
        text = _compile(
            lambda *a: paged_prefill_pallas(*a, chunk=chunk, block_q=chunk,
                                            interpret=False),
            q, pool, pool, tables, lane_pos, lane_pos, window, *scales)
        name = "paged_attention_prefill"
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert any(name in ln for ln in calls), calls


@pytest.mark.parametrize("k,n", [(2048, 11008), (11008, 2048)])
@pytest.mark.parametrize("bits", [8, 4])
def test_bitplane_gemv_compiles(one_chip, bits, k, n):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compile(
        lambda p, s, x: bitplane_gemv(p, s, x, bits=bits, interpret=False,
                                      out_dtype=jnp.bfloat16),
        sds((k * bits // 8, n), jnp.int8), sds((1, n), jnp.float32),
        sds((LANES, k), jnp.bfloat16))
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert any("bitplane_gemv" in ln for ln in calls), calls
