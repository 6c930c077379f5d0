"""Backend registry for the IMAGine GEMV engine.

One dispatch table replaces the ``use_pallas=`` / ``interpret=`` boolean
pairs that used to be sprinkled over models/, serve/, launch/ and
benchmarks/.  A backend is a function

    fn(plan: EnginePlan, lin: PackedLinear, x, out_dtype) -> y

registered under a string name.  Shipped backends:

  ``reference``        pure-jnp unpack + einsum — exact, runs anywhere;
                       the dry-run lowering path.
  ``bit_serial``       explicit digit-plane walk (radix 1/2/4), numerically
                       identical to ``reference``; the FPGA-faithful oracle.
  ``pallas_interpret`` the Pallas kernel body interpreted on CPU — used to
                       validate the TPU kernel off-hardware.
  ``pallas_tpu``       the Pallas kernel compiled for TPU hardware.
  ``sharded``          mesh-native wrapper (``repro.engine.sharded``):
                       shard_maps any of the above over the plan's model
                       axis — column/row-parallel PackedLinear shards,
                       row partials psum-reduced.

``auto`` resolves from ``jax.default_backend()`` at plan-resolution time:
TPU hosts get ``pallas_tpu``, everything else gets ``reference``.
"""

from __future__ import annotations

import warnings
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.engine.packed import PackedLinear

BackendFn = Callable[..., jnp.ndarray]

_REGISTRY: Dict[str, BackendFn] = {}

AUTO = "auto"


def register_backend(name: str, fn: BackendFn = None):
    """Register ``fn`` as engine backend ``name`` (usable as a decorator)."""
    if fn is None:
        return lambda f: register_backend(name, f)
    if not isinstance(name, str) or not name:
        raise ValueError(f"backend name must be a non-empty string: {name!r}")
    _REGISTRY[name] = fn
    return fn


def get_backend(name: str) -> BackendFn:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown engine backend {name!r}; available: "
            f"{sorted(_REGISTRY)}") from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def default_backend() -> str:
    """Auto-selection: the compiled Pallas kernel on TPU hosts, the exact
    jnp reference everywhere else (Pallas TPU kernels do not lower on the
    CPU backend)."""
    return "pallas_tpu" if jax.default_backend() == "tpu" else "reference"


def resolve_backend_name(name: str = AUTO) -> str:
    resolved = default_backend() if name in (AUTO, None, "") else name
    if resolved not in _REGISTRY:
        raise KeyError(
            f"unknown engine backend {resolved!r}; available: "
            f"{sorted(_REGISTRY)}")
    return resolved


# ---------------------------------------------------------------------------
# paged-attention backends (the decode-attention read path)
# ---------------------------------------------------------------------------

# ``gather`` is the reference read path (materialize the logical KV view,
# then attend); the pallas names run the fused in-place kernel
# (repro.kernels.paged_attention) that reads pool pages through the block
# table.  Same naming scheme as the GEMV backends so one mental model
# covers both dispatch axes of the plan.
ATTN_BACKENDS = ("gather", "pallas_interpret", "pallas_tpu")


def default_attn_backend() -> str:
    """Auto-selection for ``EnginePlan.attn_backend``: the compiled fused
    kernel on TPU hosts, the exact gather path everywhere else (interpret
    mode is a validation tool, not a CPU fast path)."""
    return "pallas_tpu" if jax.default_backend() == "tpu" else "gather"


def resolve_attn_backend(name: str = AUTO, *, mesh=None) -> str:
    """Resolve an attention-backend name; ``auto`` consults the host.

    ``auto`` resolves the same way with or without a mesh: TPU hosts get
    the fused kernel (``pallas_tpu``), everything else ``gather``.  The
    kernel shard_maps over the plan's model axis
    (``repro.engine.sharded.sharded_paged_attention`` — KV heads are
    already the ``model``-sharded dim of the page pool), so a
    mesh-carrying TPU plan now runs fused by default; the old downgrade
    of ``auto``-on-mesh to ``gather`` is gone.  ``mesh`` is still
    accepted so plan resolution reads naturally at call sites, but no
    longer changes the answer — ``gather`` stays the reference backend
    everywhere and an explicit name is always honored.
    """
    del mesh  # no longer affects resolution (kept for call-site compat)
    if name in (AUTO, None, ""):
        resolved = default_attn_backend()
    else:
        resolved = name
    if resolved not in ATTN_BACKENDS:
        raise KeyError(
            f"unknown attention backend {resolved!r}; available: "
            f"{sorted(ATTN_BACKENDS)}")
    return resolved


def default_interpret() -> bool:
    """Should Pallas kernel bodies run in interpret mode on this host?

    True everywhere except real TPU hardware.  Kernel wrappers
    (``repro.kernels.*.ops``) call this when the caller does not pin the
    mode, so the same call-site works on CPU (validation) and TPU (prod).
    """
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """Normalize a kernel wrapper's ``interpret`` argument: None means
    "ask the registry" (:func:`default_interpret`), a bool is explicit."""
    return default_interpret() if interpret is None else bool(interpret)


# ---------------------------------------------------------------------------
# shipped backends
# ---------------------------------------------------------------------------


@register_backend("reference")
def _reference(plan, lin: PackedLinear, x: jnp.ndarray, out_dtype):
    """Unpack-in-register + einsum at fp32 accumulation.  Exact for b<=8.

    Handles stacked weights: a ``(..., Kp, N)`` packed tensor broadcasts
    against ``(..., B?, K)`` activations through ``jnp.matmul`` semantics —
    the MoE expert-parallel path uses ``(E, Kp, N) @ (B, E, C, K)``.
    """
    from repro.core.bitplane import unpack_weights

    q = unpack_weights(lin.packed, lin.bits, axis=-2)
    if lin.packed.ndim == 2:
        acc = jnp.einsum(
            "...k,kn->...n",
            x.astype(jnp.float32),
            q.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
    else:
        acc = jnp.matmul(x.astype(jnp.float32), q.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
    return (acc * lin.scale).astype(out_dtype)


@register_backend("bit_serial")
def _bit_serial(plan, lin: PackedLinear, x: jnp.ndarray, out_dtype):
    """Digit-serial oracle: walks ``radix``-bit planes of the two's
    complement code exactly like the FPGA engine retires them, the top
    digit carrying negative weight.  Numerically identical to
    ``reference``; exists so the paper's PE-variant sweep (radix-2 Booth,
    slice4, nibble-serial) has an executable host-side twin.
    """
    from repro.core.bitplane import unpack_weights

    bits, radix = lin.bits, plan.radix
    if bits % radix != 0:
        raise ValueError(f"radix {radix} must divide bits {bits}")
    q = unpack_weights(lin.packed, bits, axis=-2)
    u = q.astype(jnp.int32) & ((1 << bits) - 1)  # two's complement code
    n_digits = bits // radix
    xf = x.astype(jnp.float32)
    acc = None
    for d in range(n_digits):
        digit = (u >> (d * radix)) & ((1 << radix) - 1)
        weight = float(1 << (d * radix))
        if d == n_digits - 1:
            sign_bit = (digit >> (radix - 1)) & 1
            digit = digit - (sign_bit << radix)
        partial = jnp.matmul(xf, digit.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
        acc = weight * partial if acc is None else acc + weight * partial
    return (acc * lin.scale).astype(out_dtype)


def _pallas(plan, lin: PackedLinear, x: jnp.ndarray, out_dtype,
            interpret: bool):
    from repro.kernels.bitplane_gemv.ops import bitplane_gemv

    if lin.packed.ndim != 2:
        # stacked (E, Kp, N) expert weights: the kernel is a 2-D GEMV tile
        # engine and has no expert grid axis yet, so these alone take the
        # exact jnp path — said out loud, once per traced shape.
        warnings.warn(
            f"bit-plane kernel: stacked weight {lin.packed.shape} runs on "
            "the jnp reference backend (the kernel handles 2-D weights "
            "only)", stacklevel=2)
        return _reference(plan, lin, x, out_dtype)
    # (B, D) decode rows and (B, C, D) prefill chunks alike: the wrapper
    # flattens leading activation dims onto the kernel's batch axis
    return bitplane_gemv(
        lin.packed, lin.scale, x,
        bits=lin.bits, radix=plan.radix,
        block_b=plan.block_b, block_n=plan.block_n, block_k=plan.block_k,
        interpret=interpret, out_dtype=out_dtype,
    )


@register_backend("pallas_interpret")
def _pallas_interpret(plan, lin, x, out_dtype):
    return _pallas(plan, lin, x, out_dtype, interpret=True)


@register_backend("pallas_tpu")
def _pallas_tpu(plan, lin, x, out_dtype):
    return _pallas(plan, lin, x, out_dtype, interpret=False)
