"""The trip-count-aware HLO cost analyzer: exactness on known programs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import (
    collective_bytes_from_hlo,
    model_bytes_for_cell,
    model_flops_for_cell,
    V5E,
    peaks,
    roofline_report,
)
from repro.roofline.hlo_cost import analyze_hlo_text
from repro.config import SHAPES, get_arch


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


class TestAnalyzer:
    def test_single_dot_exact(self):
        c = _compile(lambda a, b: a @ b,
                     jnp.ones((128, 64)), jnp.ones((64, 32)))
        r = analyze_hlo_text(c.as_text())
        assert r["flops"] == pytest.approx(2 * 128 * 64 * 32, rel=0.01)

    @pytest.mark.parametrize("length", [2, 5, 13])
    def test_scan_trip_count(self, length):
        x = jnp.ones((64, 64))
        c = _compile(
            lambda x: jax.lax.scan(lambda c, _: (c @ c, None), x, None,
                                   length=length)[0], x)
        r = analyze_hlo_text(c.as_text())
        assert r["flops"] == pytest.approx(length * 2 * 64**3, rel=0.01)

    def test_nested_scan(self):
        x = jnp.ones((32, 32))

        def nested(x):
            def outer(c, _):
                d, _ = jax.lax.scan(lambda d, _: (d @ d, None), c, None,
                                    length=3)
                return d, None
            return jax.lax.scan(outer, x, None, length=5)[0]

        r = analyze_hlo_text(_compile(nested, x).as_text())
        assert r["flops"] == pytest.approx(15 * 2 * 32**3, rel=0.01)

    def test_xla_cost_analysis_undercounts_scans(self):
        """Documents WHY the custom analyzer exists."""
        x = jnp.ones((64, 64))
        c = _compile(
            lambda x: jax.lax.scan(lambda c, _: (c @ c, None), x, None,
                                   length=10)[0], x)
        cost = c.cost_analysis()
        if not isinstance(cost, dict):  # older jax returns [dict]
            cost = cost[0]
        xla = cost["flops"]
        ours = analyze_hlo_text(c.as_text())["flops"]
        assert ours > 5 * xla  # XLA counts the body once

    def test_bytes_scale_with_trips(self):
        x = jnp.ones((64, 64))
        rs = []
        for length in (2, 8):
            c = _compile(
                lambda x, n=length: jax.lax.scan(
                    lambda c, _: (c @ c + 1.0, None), x, None, length=n)[0], x)
            rs.append(analyze_hlo_text(c.as_text())["bytes"])
        assert rs[1] > 2.5 * rs[0]

    def test_region_attribution(self):
        """Instructions carry op_name metadata; attention dots must be
        attributed to the 'attention' region."""
        from repro.models.attention import attend_flash
        q = jnp.ones((1, 128, 4, 16))
        k = jnp.ones((1, 128, 2, 16))
        pos = jnp.broadcast_to(jnp.arange(128), (1, 128))
        c = _compile(lambda q, k, v: attend_flash(q, k, v, pos, 0,
                                                  block_q=64, block_kv=64),
                     q, k, k)
        r = analyze_hlo_text(c.as_text())
        assert "attention" in r["regions"]
        assert r["regions"]["attention"]["flops"] > 0
        # most of the program's flops are attention here
        assert r["regions"]["attention"]["flops"] > 0.5 * r["flops"]


class TestCollectiveParse:
    def test_parses_families(self):
        text = """
HloModule m
ENTRY %main (a: f32[16,16]) -> f32[16,16] {
  %a = f32[16,16]{1,0} parameter(0)
  %ag = f32[64,16]{1,0} all-gather(%a), replica_groups={}
  %ar = f32[16,16]{1,0} all-reduce(%a), to_apply=%add
  %rs = f32[4,16]{1,0} reduce-scatter(%a), to_apply=%add
  %aa = f32[16,16]{1,0} all-to-all(%a)
  ROOT %cp = f32[16,16]{1,0} collective-permute(%a)
}
"""
        r = collective_bytes_from_hlo(text)
        assert r["all-gather"] == 64 * 16 * 4
        assert r["all-reduce"] == 16 * 16 * 4
        assert r["reduce-scatter"] == 4 * 16 * 4
        assert r["all-to-all"] == 16 * 16 * 4
        assert r["collective-permute"] == 16 * 16 * 4
        assert r["total"] == sum(
            r[k] for k in ("all-gather", "all-reduce", "reduce-scatter",
                           "all-to-all", "collective-permute"))


class TestRooflineReport:
    def test_report_fields(self):
        c = _compile(lambda a, b: a @ b,
                     jnp.ones((256, 256)), jnp.ones((256, 256)))
        rep = roofline_report(c, 1, device_kind=V5E,
                              model_flops=2 * 256**3,
                              model_bytes=3 * 256 * 256 * 4)
        for key in ("compute_s", "memory_s", "collective_s", "dominant",
                    "roofline_fraction", "useful_flops_ratio"):
            assert key in rep
        assert rep["dominant"] in ("compute_s", "memory_s", "collective_s")
        assert 0 < rep["roofline_fraction"] <= 1.5

    def test_peaks_keyed_by_device_kind(self):
        """The v5e peaks carry their published values; a device kind the
        table lacks (the CPU this runs on, say) is an error, not a silent
        v5e default."""
        assert peaks(V5E)["peak_flops"] == 197e12
        assert peaks(V5E)["hbm_bw"] == 819e9
        c = _compile(lambda a: a * 2, jnp.ones((8, 8)))
        for kind in ("cpu", "TPU v4"):
            with pytest.raises(KeyError, match="no published peaks"):
                peaks(kind)
            with pytest.raises(KeyError):
                roofline_report(c, 1, device_kind=kind)

    def test_model_flops_conventions(self):
        cfg = get_arch("mistral-large-123b")
        n = cfg.param_count()
        assert model_flops_for_cell(cfg, SHAPES["train_4k"]) == pytest.approx(
            6 * n * 256 * 4096)
        assert model_flops_for_cell(cfg, SHAPES["decode_32k"]) == pytest.approx(
            2 * n * 128)
        moe = get_arch("qwen3-moe-235b-a22b")
        assert (model_flops_for_cell(moe, SHAPES["decode_32k"])
                < 2 * moe.param_count() * 128 * 0.5)  # active << total

    def test_model_bytes_engine_scaling(self):
        cfg = get_arch("gemma3-27b")
        b16 = model_bytes_for_cell(cfg, SHAPES["decode_32k"], 0)
        b8 = model_bytes_for_cell(cfg, SHAPES["decode_32k"], 8)
        b4 = model_bytes_for_cell(cfg, SHAPES["decode_32k"], 4)
        assert b8 == pytest.approx(b16 / 2, rel=0.01)
        assert b4 == pytest.approx(b16 / 4, rel=0.01)
