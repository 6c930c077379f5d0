"""Property-style tests for the sharding layer's divisibility discipline:
``_divisible_prefix``, the degrade-to-replication rule of ``param_spec``,
and the hint filter — across awkward (prime, non-divisible, oversized)
mesh shapes.  The invariant under test: non-divisible dimensions must
*never* error, only degrade to replication, and any axis that is placed
must exactly divide its dimension."""

from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.dist.hints import _filter_entry
from repro.dist.sharding import (
    _divisible_prefix,
    param_spec,
    pool_pages_for_mesh,
)


class FakeMesh:
    """Stand-in accepted by the spec functions: axis_names + name->size."""

    def __init__(self, **sizes):
        self.axis_names = tuple(sizes)
        self.shape = dict(sizes)


# ---------------------------------------------------------------------------
# _divisible_prefix
# ---------------------------------------------------------------------------


@settings(max_examples=60)
@given(dim=st.integers(-4, 4096), pod=st.integers(1, 9),
       data=st.integers(1, 17))
def test_divisible_prefix_invariants(dim, pod, data):
    axes = ("pod", "data")
    sizes = {"pod": pod, "data": data}
    kept = _divisible_prefix(dim, axes, sizes)
    # a prefix, never a reordering or subset-with-gaps
    assert kept == axes[:len(kept)]
    if dim <= 0:
        assert kept == ()
        return
    # whatever was kept divides the dimension exactly
    prod = 1
    for a in kept:
        prod *= sizes[a]
    assert dim % prod == 0
    # and it is maximal: adding the next axis would break divisibility
    if len(kept) < len(axes):
        nxt = prod * sizes[axes[len(kept)]]
        assert dim % nxt != 0


@settings(max_examples=30)
@given(n=st.integers(1, 200), pod=st.integers(1, 7), data=st.integers(1, 7))
def test_pool_padding_minimal_and_divisible(n, pod, data):
    mesh = FakeMesh(pod=pod, data=data, model=3)
    padded = pool_pages_for_mesh(n, mesh)
    assert padded >= n
    assert padded % (pod * data) == 0
    assert padded - n < pod * data  # minimal padding


# ---------------------------------------------------------------------------
# param_spec degrade-to-replication
# ---------------------------------------------------------------------------

_OWNERS = ["wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
           "embed", "in_proj", "out_proj", "norm"]


@settings(max_examples=80)
@given(owner=st.sampled_from(_OWNERS),
       d_in=st.integers(1, 96), d_out=st.integers(1, 96),
       model=st.integers(1, 13), data=st.integers(1, 13),
       leafname=st.sampled_from(["w", "packed", "scale", "bias"]))
def test_param_spec_never_errors_and_divides(owner, d_in, d_out, model,
                                             data, leafname):
    mesh = FakeMesh(data=data, model=model)
    shape = (d_out,) if leafname == "bias" else (d_in, d_out)
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32)
    path = (jax.tree_util.DictKey("layers"), jax.tree_util.DictKey(owner),
            jax.tree_util.DictKey(leafname))
    spec = param_spec(path, leaf, mesh)           # must never raise
    assert len(spec) <= leaf.ndim
    for ax, entry in enumerate(spec):
        if entry is None:
            continue
        assert entry == "model"
        assert leaf.shape[ax] % model == 0        # placed => divides


@settings(max_examples=40)
@given(model=st.integers(2, 12), e=st.integers(1, 24),
       d=st.integers(8, 64))
def test_param_spec_stacked_experts_degrade(model, e, d):
    """Stacked (L, E, D, F) expert weights: the expert axis is sharded
    over model iff divisible, otherwise fully replicated — never an
    error, never a half-sharded surprise on another axis."""
    mesh = FakeMesh(data=1, model=model)
    leaf = jax.ShapeDtypeStruct((2, e, d, d), jnp.float32)
    path = (jax.tree_util.DictKey("layers"), jax.tree_util.DictKey("moe"),
            jax.tree_util.DictKey("w_up"))
    spec = param_spec(path, leaf, mesh)
    if e % model == 0:
        assert spec[1] == "model"
    else:
        assert all(s is None for s in spec)


# ---------------------------------------------------------------------------
# hint filtering (with_hint's divisibility filter)
# ---------------------------------------------------------------------------


@settings(max_examples=60)
@given(dim=st.integers(1, 256), pod=st.integers(1, 9),
       data=st.integers(1, 9), unknown=st.booleans())
def test_filter_entry_degrades(dim, pod, data, unknown):
    axes = {"pod": pod, "data": data}
    entry = ("pod", "nope", "data") if unknown else ("pod", "data")
    kept = _filter_entry(entry, dim, axes)
    if kept is None:
        return
    names = (kept,) if isinstance(kept, str) else tuple(kept)
    assert "nope" not in names
    prod = 1
    for n in names:
        prod *= axes[n]
    assert dim % prod == 0
