#!/usr/bin/env python
"""Training driver with the full production loop: deterministic sharded
data, AdamW + cosine schedule, gradient accumulation, checkpointing with
auto-resume, a simulated node failure mid-run, and straggler monitoring.

Default is a ~5M-param qwen2.5-family model for CPU friendliness; pass
--arch/--scale to grow it (the same driver lowers the full configs on the
production mesh via launch/dryrun.py).

    PYTHONPATH=src python examples/train_lm.py --steps 60
"""

import argparse
import dataclasses
import os

import jax

from repro.ckpt import CheckpointManager
from repro.config import get_reduced
from repro.config.base import TrainConfig
from repro.data import DataPipeline
from repro.ft import FailureInjector, StragglerMonitor
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.train import Trainer


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--inject-failure", type=int, default=-1,
                    help="step at which to simulate a node failure")
    args = ap.parse_args()

    cfg = dataclasses.replace(
        get_reduced(args.arch),
        dtype="float32",
        d_model=args.width,
        n_layers=args.layers,
        d_ff=args.width * 3,
        vocab_size=4096,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    n = sum(l.size for l in jax.tree.leaves(params))
    print(f"model: {cfg.name} {n:,} params")

    tcfg = TrainConfig(lr=3e-4, total_steps=args.steps, warmup_steps=10,
                       microbatches=2)
    pipe = DataPipeline(cfg, batch=args.batch, seq_len=args.seq, seed=0)
    os.makedirs(args.ckpt_dir, exist_ok=True)
    injector = None
    if args.inject_failure >= 0:
        injector = FailureInjector(schedule={args.inject_failure: 0})

    tr = Trainer(
        cfg, tcfg, params, pipe,
        ckpt_manager=CheckpointManager(args.ckpt_dir, keep=2),
        ckpt_every=20,
        straggler_monitor=StragglerMonitor(),
        failure_injector=injector,
    )
    hist = tr.run(args.steps)["loss"]
    print(f"trained {len(hist)} steps (restarts={tr.restarts}): "
          f"loss {hist[0]:.4f} -> {hist[-1]:.4f}")
    stragglers = tr.straggler.chronic_hosts()
    print(f"chronic stragglers: {stragglers or 'none'}")
    print(f"checkpoints under {args.ckpt_dir}: resume by re-running")


if __name__ == "__main__":
    main()
