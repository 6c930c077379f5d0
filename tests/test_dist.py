"""Distribution layer: sharding rules, mesh construction, multi-device
numerics.  Multi-device tests run in a subprocess with 8 forced host
devices so this process's single-device view is untouched."""

import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.config.base import MeshConfig
from repro.dist import make_mesh, use_mesh
from repro.dist.sharding import (
    batch_shardings,
    cache_shardings,
    param_spec,
    param_shardings,
    pool_pages_for_mesh,
)
from repro.launch.steps import abstract_params

from conftest import reduced_f32


def _run_sub(code: str):
    pre = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, "src")
        sys.path.insert(0, "tests")
        import jax, jax.numpy as jnp
        import numpy as np
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", pre + textwrap.dedent(code)],
                         capture_output=True, text=True, cwd=repo,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestMeshConfig:
    def test_shapes(self):
        assert MeshConfig(multi_pod=False).shape == (16, 16)
        assert MeshConfig(multi_pod=True).shape == (2, 16, 16)
        assert MeshConfig(multi_pod=True).n_devices == 512
        assert MeshConfig(multi_pod=True).data_axes == ("pod", "data")


class TestParamSpecs:
    def _specs(self, arch):
        cfg = reduced_f32(arch)
        ap = abstract_params(cfg)

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        return jax.tree_util.tree_map_with_path(
            lambda p, l: param_spec(p, l, FakeMesh()), ap), cfg

    def test_dense_tp_rules(self):
        specs, cfg = self._specs("mistral-large-123b")
        # embed vocab-sharded (32768 % 16 == 0)
        assert specs["embed"] == P("model", None)
        attn = specs["layers"]["attn"]
        assert attn["wq"]["w"] == P(None, None, "model")
        assert attn["wo"]["w"] == P(None, "model", None)
        mlp = specs["layers"]["mlp"]
        assert mlp["w_gate"]["w"] == P(None, None, "model")
        assert mlp["w_down"]["w"] == P(None, "model", None)
        assert specs["final_norm"] == P(None)
        assert specs["lm_head"]["w"] == P(None, "model")

    def test_moe_expert_parallel(self):
        specs, cfg = self._specs("qwen3-moe-235b-a22b")
        moe = specs["layers"]["moe"]
        # stacked (L, E, D, F): experts axis gets the model axis when E%16==0
        e = cfg.n_experts
        expect = "model" if e % 16 == 0 else None
        assert moe["w_gate"] == P(None, expect and "model", None, None) or \
            moe["w_gate"][1] in ("model", None)

    def test_non_divisible_falls_back_to_replication(self):
        specs, cfg = self._specs("mamba2-130m")
        # in_proj width (2*di+2*st+nh) is not divisible by 16 -> replicated
        ssm = specs["layers"]["ssm"]
        assert ssm["in_proj"]["w"][-1] is None
        # out_proj (di=128 divisible? reduced: di=128 -> 128%16==0 -> sharded)
        assert ssm["out_proj"]["w"][-2] in ("model", None)

    def test_batch_sharded_on_data_axes(self):
        cfg = reduced_f32("qwen2.5-3b")
        mesh = make_mesh((1, 1), ("data", "model"))
        ab = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
        sh = batch_shardings(mesh, ab)
        assert sh["tokens"].spec == P(("data",), None)


class TestKVPagesSpecs:
    """The paged-serving pytree through the KV-cache rules: pools shard
    pages-over-data and heads-over-model, scale pools follow their K/V
    pool's head sharding, block tables / pos / active shard the lane axis
    over data only (satellite of the mesh-native refactor)."""

    def _mesh(self):
        return make_mesh((1, 1), ("data", "model"))

    def test_pool_and_scale_specs(self):
        from repro.serve.pages import init_kv_pages

        cfg = reduced_f32("qwen2.5-3b")
        pages = jax.eval_shape(
            lambda: init_kv_pages(cfg, 8, 4, kv_bits=8))
        sh = cache_shardings(self._mesh(), pages)
        # (L, Hkv, P, page, Dh): KV heads over model, pages over data
        assert sh.k.spec == P(None, "model", ("data",), None, None)
        assert sh.v.spec == sh.k.spec
        # (L, Hkv, P, page): the scale pool's head axis must match its
        # K/V pool (an unsharded scale would desync dequant)
        assert sh.k_scale.spec == P(None, "model", ("data",), None)
        assert sh.v_scale.spec == sh.k_scale.spec

    def test_page_state_specs(self):
        state = {
            "block_tables": jax.ShapeDtypeStruct((4, 8), jnp.int32),
            "pos": jax.ShapeDtypeStruct((4,), jnp.int32),
            "active": jax.ShapeDtypeStruct((4,), jnp.bool_),
        }
        sh = cache_shardings(self._mesh(), state)
        assert sh["block_tables"].spec == P(("data",), None)
        assert sh["pos"].spec == P(("data",))
        assert sh["active"].spec == P(("data",))

    def test_pool_padding_for_mesh(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        assert pool_pages_for_mesh(9, mesh) == 9  # data product 1: no pad
        assert pool_pages_for_mesh(9, None) == 9

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 4, "model": 2}

        assert pool_pages_for_mesh(9, FakeMesh()) == 12
        assert pool_pages_for_mesh(12, FakeMesh()) == 12


class TestMultiDevice:
    def test_sharded_train_step_matches_single_device(self):
        """(2,4) mesh train step == single-device train step numerics."""
        _run_sub("""
        from conftest import reduced_f32, make_batch
        from repro.models import init_params
        from repro.config.base import TrainConfig
        from repro.train.trainer import make_train_step
        from repro.optim import make_optimizer
        from repro.launch.steps import _attach
        from repro.dist import make_mesh, use_mesh
        from repro.dist.sharding import param_shardings, batch_shardings, opt_state_shardings
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = reduced_f32("qwen2.5-3b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch = make_batch(cfg, jax.random.PRNGKey(1), batch=8, seq=16)
        tcfg = TrainConfig()
        step = make_train_step(cfg, tcfg, donate=False)
        init_fn, _ = make_optimizer("adamw")
        opt = init_fn(params)

        # single device
        p1, o1, _, m1 = step(params, opt, {}, batch)

        # sharded
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_mesh(mesh):
            ps = param_shardings(mesh, params)
            params_s = jax.device_put(params, ps)
            opt_s = jax.device_put(opt, opt_state_shardings(mesh, opt))
            batch_s = jax.device_put(batch, batch_shardings(mesh, batch))
            p2, o2, _, m2 = step(params_s, opt_s, {}, batch_s)
        l1, l2 = float(m1["loss"]), float(m2["loss"])
        assert abs(l1 - l2) / abs(l1) < 1e-4, (l1, l2)
        import numpy as np
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(jax.device_get(b)), rtol=2e-3, atol=2e-3)
        print("sharded == single-device OK", l1, l2)
        """)

    def test_compressed_psum_matches_plain(self):
        _run_sub("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.dist import make_mesh
        from repro.dist.collectives import compressed_psum_leaf

        mesh = make_mesh((8,), ("pod",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

        @partial(jax.shard_map, mesh=mesh, in_specs=P("pod"),
                 out_specs=P("pod"))
        def plain(x):
            return jax.lax.psum(x, "pod")

        @partial(jax.shard_map, mesh=mesh, in_specs=P("pod"),
                 out_specs=P("pod"))
        def comp(x):
            return compressed_psum_leaf(x, "pod", bits=8)

        a, b = plain(g), comp(g)
        scale = float(jnp.max(jnp.abs(a)))
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 0.05, err
        print("compressed psum rel err", err)
        """)

    def test_compressed_psum_bits4_exactness_bound(self):
        """bits=4: pin the docstring's bound — every participant rounds by
        at most scale/2 with the shared scale = pmax(absmax)/qmax, qmax =
        2^(4-1)-1 = 7, so |comp - plain| <= n_dev * scale / 2.  And on an
        integer grid whose absmax is exactly qmax the scale is 1.0 and the
        4-bit wire is lossless."""
        _run_sub("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.dist import make_mesh
        from repro.dist.collectives import compressed_psum_leaf

        mesh = make_mesh((8,), ("pod",))

        def pair(x, bits):
            @partial(jax.shard_map, mesh=mesh, in_specs=P("pod"),
                     out_specs=P("pod"))
            def plain(v):
                return jax.lax.psum(v, "pod")

            @partial(jax.shard_map, mesh=mesh, in_specs=P("pod"),
                     out_specs=P("pod"))
            def comp(v):
                return compressed_psum_leaf(v, "pod", bits=bits)

            return plain(x), comp(x)

        g = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
        a, b = pair(g, 4)
        absmax = float(jnp.max(jnp.abs(g)))      # pmax of shard maxes
        bound = 8 * (absmax / 7.0) / 2.0
        err = float(jnp.max(jnp.abs(a - b)))
        assert err <= bound * 1.0001, (err, bound)
        print("bits=4 err", err, "<= bound", bound)

        gi = np.random.default_rng(0).integers(-7, 8, (8, 32))
        gi = jnp.asarray(gi.astype(np.float32)).at[0, 0].set(7.0)
        a2, b2 = pair(gi, 4)
        np.testing.assert_array_equal(np.asarray(a2), np.asarray(b2))
        print("bits=4 integer grid lossless")
        """)

    def test_serve_step_sharded_decode(self):
        """Decode with sequence-sharded 'model' axis matches single-dev."""
        _run_sub("""
        from conftest import reduced_f32
        from repro.models import init_params, init_cache, decode_step
        from repro.dist import make_mesh, use_mesh
        from repro.dist.sharding import param_shardings, cache_shardings
        cfg = reduced_f32("gemma3-27b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        cache = init_cache(cfg, 2, max_len=16)
        tok = jnp.ones((2, 1), jnp.int32)
        l1, c1 = decode_step(params, cache, tok, cfg)
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_mesh(mesh):
            ps = jax.device_put(params, param_shardings(mesh, params))
            cs = jax.device_put(cache, cache_shardings(mesh, cache))
            l2, c2 = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))(ps, cs, tok)
        import numpy as np
        np.testing.assert_allclose(np.asarray(l1), np.asarray(jax.device_get(l2)), rtol=2e-4, atol=2e-4)
        print("sharded decode OK")
        """)
