#!/usr/bin/env python
"""Quickstart: build a tiny gemma3-family model, run a forward pass, take a
few train steps, quantize for the IMAGine engine, decode a few tokens.

    PYTHONPATH=src python examples/quickstart.py
"""

import dataclasses

import jax
import jax.numpy as jnp

from repro.config import get_reduced
from repro.config.base import EngineConfig, TrainConfig
from repro.data import DataPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models import (
    decode_step,
    forward,
    init_cache,
    init_params,
    quantize_params,
)
from repro.optim import make_optimizer
from repro.train.trainer import make_train_step


def main():
    enable_compile_cache()
    cfg = dataclasses.replace(get_reduced("gemma3-27b"), dtype="float32")
    print(f"arch family={cfg.family} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} params={cfg.param_count():,}")

    params = init_params(cfg, jax.random.PRNGKey(0))
    pipe = DataPipeline(cfg, batch=4, seq_len=32, seed=0)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}

    logits, _ = forward(params, batch, cfg, remat="none")
    print(f"forward: logits {logits.shape}")

    tcfg = TrainConfig(lr=1e-3, total_steps=20, warmup_steps=2)
    step = make_train_step(cfg, tcfg, donate=False)
    init_fn, _ = make_optimizer(tcfg.optimizer)
    opt = init_fn(params)
    for i in range(5):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
        params, opt, _, metrics = step(params, opt, {}, batch)
        print(f"train step {i}: loss={float(metrics['loss']):.4f} "
              f"lr={float(metrics['lr']):.2e}")

    # IMAGine engine: quantize to int8 bit-planes and decode
    qparams = quantize_params(params, cfg, bits=8)
    eng = EngineConfig(weight_bits=8, backend="reference")
    cache = init_cache(cfg, batch=2, max_len=16)
    tok = jnp.asarray([[1], [2]], jnp.int32)
    for i in range(4):
        logits, cache = decode_step(qparams, cache, tok, cfg, eng)
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        print(f"decode step {i}: tokens {tok[:, 0].tolist()}")
    print("OK")


if __name__ == "__main__":
    main()
