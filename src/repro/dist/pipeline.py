"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

The two-pod production mesh can flip its ``pod`` axis from data to
pipeline parallelism (``MeshConfig.pod_axis_mode``): layers are split into
``n_stages`` contiguous stages, one stage per pod, and microbatches stream
through with ``lax.ppermute`` handing activations to the next stage each
tick — the standard fill/drain schedule (bubble fraction
``(S-1)/(M+S-1)``).

``pipeline_apply`` is exact (bitwise-equal math to running the stages
sequentially) and differentiable: the ppermute transposes to the reverse
permute, so gradients pipeline backwards through the same schedule.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.hints import active_mesh

Pytree = Any


def microbatch(x: jnp.ndarray, n_micro: int) -> jnp.ndarray:
    """(B, ...) -> (n_micro, B // n_micro, ...)."""
    b = x.shape[0]
    if b % n_micro != 0:
        raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])


def stack_stages(tree: Pytree, n_stages: int) -> Pytree:
    """Reshape each (L, ...) leaf to (n_stages, L // n_stages, ...) so the
    leading axis can be sharded one-stage-per-pod."""

    def one(a):
        l = a.shape[0]
        if l % n_stages != 0:
            raise ValueError(
                f"layer count {l} not divisible by n_stages {n_stages}")
        return a.reshape((n_stages, l // n_stages) + a.shape[1:])

    return jax.tree.map(one, tree)


def _sequential(staged_params: Pytree, micros: jnp.ndarray,
                stage_fn: Callable, n_stages: int) -> jnp.ndarray:
    h = micros
    for s in range(n_stages):
        w = jax.tree.map(lambda a: a[s], staged_params)
        h = stage_fn(w, h)
    return h


def pipeline_apply(
    staged_params: Pytree,
    micros: jnp.ndarray,
    stage_fn: Callable[[Pytree, jnp.ndarray], jnp.ndarray],
    *,
    n_stages: int,
    axis_name: str = "pod",
) -> jnp.ndarray:
    """Run ``micros`` (n_micro, mb, ...) through ``n_stages`` pipeline
    stages whose stacked params live one-per-device along ``axis_name``.

    ``stage_fn(stage_params, h) -> h`` applies one stage's layer slice.
    Returns (n_micro, mb, ...) outputs, replicated over the mesh.  Falls
    back to an exact sequential sweep when no mesh with ``axis_name`` (of
    the right size) is active — same numerics, no collectives.
    """
    mesh = active_mesh()
    if (mesh is None or axis_name not in mesh.axis_names
            or dict(mesh.shape)[axis_name] != n_stages):
        return _sequential(staged_params, micros, stage_fn, n_stages)

    n_micro = micros.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def ranked(w_local, micros):
        # w_local: (1, L/S, ...) — this rank's stage slice
        w = jax.tree.map(lambda a: a[0], w_local)
        sidx = jax.lax.axis_index(axis_name)
        state = jnp.zeros(micros.shape[1:], micros.dtype)
        outputs = jnp.zeros_like(micros)
        for t in range(n_micro + n_stages - 1):
            # stage 0 injects microbatch t (junk past the last microbatch
            # never reaches the collection window)
            x_in = jnp.where(sidx == 0, micros[min(t, n_micro - 1)], state)
            y = stage_fn(w, x_in)
            if t >= n_stages - 1:
                done = jnp.where(sidx == n_stages - 1, y, 0.0)
                outputs = outputs.at[t - (n_stages - 1)].set(
                    done.astype(outputs.dtype))
            state = jax.lax.ppermute(y, axis_name, perm)
        # only the last stage holds real outputs; psum replicates them
        return jax.lax.psum(outputs, axis_name)

    return jax.shard_map(
        ranked,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )(staged_params, micros)
