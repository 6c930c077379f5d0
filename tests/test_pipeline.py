"""Pipeline parallelism (GPipe over the pod axis): forward/gradient
exactness vs the unpipelined stack, and collective-permute lowering.
Runs on 8 forced host devices in a subprocess."""

import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np

from repro.dist.pipeline import microbatch, stack_stages


def test_microbatch_roundtrip():
    x = jnp.arange(24).reshape(8, 3)
    m = microbatch(x, 4)
    assert m.shape == (4, 2, 3)
    np.testing.assert_array_equal(np.asarray(m.reshape(8, 3)), np.asarray(x))


def test_stack_stages_shapes():
    import jax

    tree = {"w": jnp.zeros((8, 4, 4)), "b": jnp.zeros((8, 4))}
    staged = stack_stages(tree, 2)
    assert staged["w"].shape == (2, 4, 4, 4)
    assert staged["b"].shape == (2, 4, 4)
    del jax


def test_pipeline_matches_sequential_multi_device():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from repro.dist import make_mesh, use_mesh
        from repro.dist.pipeline import pipeline_apply, microbatch, stack_stages
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        L, D, B, M, S = 8, 16, 8, 4, 2
        key = jax.random.PRNGKey(0)
        Ws = jax.random.normal(key, (L, D, D)) * 0.3
        x = jax.random.normal(jax.random.fold_in(key, 1), (B, D))

        def stage_fn(local_w, h):
            h, _ = jax.lax.scan(lambda h, w: (jnp.tanh(h @ w), None), h, local_w)
            return h

        ref = stage_fn(Ws, x)
        micros = microbatch(x, M)
        staged = stack_stages(Ws, S)
        with use_mesh(mesh):
            staged_s = jax.device_put(staged, NamedSharding(mesh, P("pod")))
            out = jax.jit(lambda w, m: pipeline_apply(
                w, m, stage_fn, n_stages=S))(staged_s, micros)
            g1 = jax.jit(jax.grad(lambda w: jnp.sum(pipeline_apply(
                w, micros, stage_fn, n_stages=S) ** 2)))(staged_s)
            txt = jax.jit(lambda w, m: pipeline_apply(
                w, m, stage_fn, n_stages=S)).lower(
                staged_s, micros).compile().as_text()
        err = float(jnp.max(jnp.abs(out.reshape(B, D) - ref)))
        assert err < 1e-5, err
        g2 = jax.grad(lambda w: jnp.sum(stage_fn(w, x) ** 2))(Ws)
        gerr = float(jnp.max(jnp.abs(
            jax.device_get(g1).reshape(L, D, D) - g2)))
        assert gerr < 1e-4, gerr
        assert "collective-permute" in txt
        print("PIPELINE_TEST_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd="/root/repo", timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PIPELINE_TEST_OK" in out.stdout
