"""Tiny cells for CPU tests: the committed configuration files with the
widths cut to a size a test run holds, float32 throughout."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

SMALL = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=128, vocab_size=4096)
PROGRAM = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
               d_ff=128, vocab_size=4096)
OPEN = {"arrivals": {"kind": "poisson", "rate_per_s": 20},
        "prompt": {"median": 24, "sigma": 0.5, "min": 4, "max": 60},
        "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 20},
        "lead_s": 0.3, "drain_s": 5, "mean_gap_limit": 1e-5}
BACKLOG = {"arrivals": {"kind": "backlog", "queued_per_slot": 2},
           "prompt": {"median": 16, "sigma": 0.5, "min": 4, "max": 40},
           "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
           "mean_gap_limit": 1e-5}


def cell(config: str = "qwen2.5-3b", *, dtype: str = "float32",
         window: int = 0, traffic=None,
         attn_backend: str = "gather") -> "harness.Cell":
    with open(os.path.join(ROOT, "bench", "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    cfg.update(SMALL, torch_dtype=dtype)
    over = dict(PROGRAM, dtype=dtype)
    if "sliding_window" in cfg["program"].get("overrides", {}):
        cfg["sliding_window"] = window
        over["sliding_window"] = window
    cfg["program"]["overrides"] = over
    cfg["serve"] = dict(n_slots=4, max_len=128, page_size=8, n_pages=0,
                        prefill_chunk=16, step_tokens=36,
                        engine={"attn_backend": attn_backend})
    cfg["reference"] = dict(tokens=1024, rows=256, q_block=64,
                            min_compared=10)
    cfg["chips"], cfg["mesh"] = 1, [1, 1]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return harness.Cell(
        "tiny", 1, cfg, traffic or OPEN,
        [m["name"] for m in bench["end_to_end"]],
        [m["name"] for m in bench["per_layer"]])


CPU_PEAK = {"flops": 1e12, "hbm_bw": 1e11}


def run(c, monkeypatch, seed: int = 2 ** 31 + 5, seconds: float = 1.0,
        **kw):
    """One harness run on the CPU: no look for a chip, made-up peaks."""
    import time

    monkeypatch.setattr(harness, "peaks", lambda kind: CPU_PEAK)
    return harness.run(c, seed, seconds, False, time.perf_counter(),
                       harness.CompileCounter(), log=lambda m: None, **kw)
