#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU, at a model's published widths.

    python chip_smoke.py              # one chip: qwen2.5-3b, two phases
    python chip_smoke.py --chips 4    # four chips: starcoder2-15b on a
                                      # (data=1, model=4) mesh, nothing else

Weights are random, drawn from ``--seed``; no checkpoint is needed.  Every
phase drives ``ServeEngine`` the way a user does (``submit`` + ``run``) and
checks what comes out:

* every request finishes with in-vocabulary tokens and finite logits;
* logits agree with ``models.transformer.forward`` on the same tokens, on
  the same chip(s): the logits the engine drew each request's last token
  from against the forward over the prompt and every token before it,
  and its prefill logits (a second wave of the same prompts,
  ``max_new_tokens=0``, served from the prefix cache) against the
  forward over the prompt.  The engine samples inside its jitted step;
  to keep the logits it is given its own host sampler as an override,
  which makes it copy them;
* the compiled decode and prefill steps contain the Pallas kernels
  (``tpu_custom_call``): the fused paged attention, and in the int8 phase
  the bit-plane GEMV.

One chip, phase A: dense bf16 weights, ``attn_backend="auto"``.  Phase B:
``EngineConfig(weight_bits=8, kv_bits=8)``, also checked against the same
engine on ``backend="reference"`` / ``attn_backend="gather"``.

Timings printed here are smoke timings of one cold process (compilation
included), not benchmark numbers.  The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
check passed; the script exits non-zero, printing no such line, when JAX
finds no TPU or any check fails.  It starts no other process.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Logit tolerances, as a share of the reference's largest |logit|.
# bf16 keeps 8 significand bits (one step is 0.4% of a value).  The
# serving path and ``forward`` round the same math in a different order at
# every one of the model's layers (chunked paged prefill and one-token
# decode against one whole-sequence pass; online softmax against a dense
# one), so each logit may move by a few bf16 steps of the largest one.
# 5% allows about a dozen; a wrong page, mask, position or scale moves
# logits by the order of the logits themselves and fails it.
TOL_BF16 = 0.05
# ``forward`` keeps K/V in bf16 while kv_bits=8 pages round every K/V entry
# to int8 against its (token, head) absmax: up to 0.4% of that absmax per
# entry, at every layer — twice the bf16 budget covers it.
TOL_INT8_KV = 0.10

PAGE = 16
N_SLOTS = 8
MAX_NEW = 16


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, ok, what):
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)


def _prompts(rng, vocab, lengths, shared):
    """Random prompts of the given lengths; the two at ``shared`` indices
    open with the same 256-token prefix."""
    prefix = rng.integers(0, vocab, 256).tolist()
    out = []
    for i, n in enumerate(lengths):
        if i in shared:
            out.append(prefix + rng.integers(0, vocab, n - 256).tolist())
        else:
            out.append(rng.integers(0, vocab, n).tolist())
    return out


def _serve(eng, prompts, max_new, vocab, check, label):
    """Submit ``prompts`` and drive the engine until it drains; returns
    ``[(tokens, logits)]``: the sequence the final logits condition on
    (the prompt and every token before the last) and those logits as
    float32."""
    import functools

    import numpy as np

    eng._sample_next = functools.partial(type(eng)._sample_next, eng)
    reqs = [eng.submit(p, max_new) for p in prompts]
    eng.run()
    check(all(r.done and r.finish_reason == "length" for r in reqs),
          f"{label}: all {len(reqs)} requests finished")
    check(all(len(r.output) == max_new for r in reqs),
          f"{label}: each emitted {max_new} tokens")
    check(all(0 <= t < vocab for r in reqs for t in r.output),
          f"{label}: tokens in [0, {vocab})")
    out = []
    for r in reqs:
        lg = np.asarray(r.last_logits, np.float32)
        out.append((list(r.prompt) + list(r.output[:-1]), lg))
    check(all(lg.shape == (vocab,) and np.isfinite(lg).all()
              for _, lg in out), f"{label}: logits finite, shape ({vocab},)")
    return out


def _reference(cfg, eng_cfg, s_pad):
    """``forward`` over one sequence padded to ``s_pad`` (one compile for
    every length; causal attention keeps the padding out of the real
    positions), returning the logits at its last real position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import forward

    @jax.jit
    def last_logits(params, tokens, last):
        logits, _ = forward(params, {"tokens": tokens}, cfg, eng_cfg)
        return logits[0, last]

    def run(params, seq):
        toks = np.zeros((1, s_pad), np.int32)
        toks[0, :len(seq)] = seq
        return np.asarray(last_logits(params, jnp.asarray(toks),
                                      jnp.int32(len(seq) - 1)), np.float32)

    return run


def _compare(label, got, want, tol, check):
    import numpy as np

    errs = [float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
            for g, w in zip(got, want)]
    agree = sum(int(np.argmax(g) == np.argmax(w)) for g, w in zip(got, want))
    print(f"  {label}: worst max|dlogit|/max|logit| = {max(errs):.4g} "
          f"(mean over requests {sum(errs) / len(errs):.4g}), argmax agrees "
          f"{agree}/{len(errs)}", flush=True)
    check(max(errs) <= tol, f"{label}: within tolerance {tol}")


def _kernel_calls(eng):
    """Pallas kernel calls in the engine's compiled decode and prefill
    steps, by kernel name.  Lowered with the arrays the engine itself
    passes, so the persistent compilation cache serves the executables
    the run just built."""
    import jax.numpy as jnp
    import numpy as np

    bt, pos = eng.alloc.device_tables(eng._table_shardings)
    n = eng.n_slots
    with eng._mesh_ctx():
        steps = {
            "decode": eng._decode_paged.lower(
                eng.params, eng.pages, bt, pos,
                jnp.asarray(np.zeros((n,), bool)),
                jnp.asarray(np.zeros((n, 1), np.int32)),
                eng.key).compile(),
            "prefill": eng._prefill_paged.lower(
                eng.params, eng.pages, bt,
                jnp.asarray(np.zeros((n, eng.prefill_chunk), np.int32)),
                pos, pos, eng.key).compile(),
        }
    out = {}
    for step, compiled in steps.items():
        lines = [ln for ln in compiled.as_text().splitlines()
                 if "tpu_custom_call" in ln]
        out[step] = {
            name: sum(name in ln for ln in lines)
            for name in ("paged_attention_decode", "paged_attention_prefill",
                         "bitplane_gemv")}
        out[step]["tpu_custom_call"] = len(lines)
    return out


def _phase(label, cfg, params, scfg, prompts, *, max_len, check, stats,
           reference, tol, mesh=None, gemv=False):
    """One serving phase: the decode wave, then the prefill wave from the
    prefix cache; returns the engine and both waves' (tokens, logits)."""
    from repro.dist import use_mesh
    from repro.serve import ServeEngine

    print(f"== phase {label}", flush=True)
    c0, t0 = stats.compile_s, time.perf_counter()
    eng = ServeEngine(cfg, params, scfg, n_slots=N_SLOTS, max_len=max_len,
                      mesh=mesh)
    print(f"  engine: attn_backend={eng.attn_backend} plan="
          f"{None if eng.plan is None else eng.plan.backend} "
          f"kv_bits={eng.kv_bits} pool={eng.pages.nbytes() / 1e9:.3f} GB",
          flush=True)
    check(eng.attn_backend == "pallas_tpu",
          f"{label}: attn_backend 'auto' resolved to the fused kernel")
    decode = _serve(eng, prompts, MAX_NEW, cfg.vocab_size, check,
                    f"{label} decode wave")
    prefill = _serve(eng, prompts, 0, cfg.vocab_size, check,
                     f"{label} prefill wave")
    stats_now = eng.prefix_stats()
    check(stats_now["hits"] >= len(prompts),
          f"{label}: prefill wave served from the prefix cache "
          f"({stats_now['hits']} hits)")
    wall = time.perf_counter() - t0
    print(f"  smoke timing (cold process, not a benchmark): phase wall "
          f"{wall:.1f} s, of which backend compile {stats.compile_s - c0:.1f}"
          f" s", flush=True)

    calls = _kernel_calls(eng)
    print(f"  compiled kernel calls: {json.dumps(calls)}", flush=True)
    for step in ("decode", "prefill"):
        check(calls[step][f"paged_attention_{step}"] >= 1,
              f"{label}: fused paged attention in the compiled {step} step")
        if gemv:
            check(calls[step]["bitplane_gemv"] >= 1,
                  f"{label}: bit-plane GEMV in the compiled {step} step")

    with use_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        for wave, got in (("decode", decode), ("prefill", prefill)):
            want = [reference(params, seq) for seq, _ in got]
            _compare(f"{label} {wave} logits vs forward",
                     [lg for _, lg in got], want, tol, check)
    return eng, decode, prefill


def _one_chip(args, check, stats):
    import gc

    import jax
    import numpy as np

    from repro.config import get_arch
    from repro.config.base import EngineConfig, ServeConfig
    from repro.models import init_params, quantize_params

    cfg = get_arch("qwen2.5-3b")
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}, {cfg.param_count() / 1e9:.2f} B "
          f"params (random, seed {args.seed})", flush=True)
    rng = np.random.default_rng(args.seed)
    lengths = [64, 128, 200, 256 + 48, 256 + 80, 512, 777, 1024]
    prompts = _prompts(rng, cfg.vocab_size, lengths, shared=(3, 4))
    s_pad = -(-(max(lengths) + MAX_NEW) // 64) * 64
    max_len = 4096

    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    print(f"init params on device: {time.perf_counter() - t0:.1f} s",
          flush=True)

    scfg = ServeConfig(max_new_tokens=MAX_NEW, page_size=PAGE,
                       prefix_cache=True)
    eng, _, _ = _phase("A (bf16 weights, bf16 KV)", cfg, params, scfg,
                       prompts, max_len=max_len, check=check, stats=stats,
                       reference=_reference(cfg, None, s_pad), tol=TOL_BF16)
    del eng
    gc.collect()

    qparams = jax.jit(quantize_params, static_argnums=(1, 2),
                      donate_argnums=0)(params, cfg, 8)
    del params
    eng_b = EngineConfig(weight_bits=8, kv_bits=8)
    scfg = ServeConfig(max_new_tokens=MAX_NEW, page_size=PAGE,
                       prefix_cache=True, engine=eng_b)
    # the same packed weights through the exact jnp reference backend
    ref_cfg = EngineConfig(weight_bits=8, backend="reference")
    eng, decode, prefill = _phase(
        "B (weight_bits=8, kv_bits=8)", cfg, qparams, scfg, prompts,
        max_len=max_len, check=check, stats=stats,
        reference=_reference(cfg, ref_cfg, s_pad), tol=TOL_INT8_KV,
        gemv=True)
    print(f"  plan: {eng.plan.backend} bits={eng.plan.bits} "
          f"kv_bits={eng.plan.kv_bits}", flush=True)
    check(eng.plan.backend == "pallas_tpu",
          "B: GEMV backend 'auto' resolved to the compiled kernel")
    del eng
    gc.collect()

    # phase B against the same engine on the reference backends: the
    # reference engine prefills each sequence the kernel engine saw, so
    # its last-position logits line up with the kernel engine's
    ref_engine = ServeConfig(max_new_tokens=0, page_size=PAGE,
                             engine=EngineConfig(weight_bits=8, kv_bits=8,
                                                 backend="reference",
                                                 attn_backend="gather"))
    from repro.serve import ServeEngine

    ref = ServeEngine(cfg, qparams, ref_engine, n_slots=N_SLOTS,
                      max_len=max_len)
    print(f"  reference engine: plan={ref.plan.backend} "
          f"attn_backend={ref.attn_backend}", flush=True)
    want = _serve(ref, [seq for seq, _ in decode + prefill], 0,
                  cfg.vocab_size, check, "B reference engine")
    _compare("B logits vs reference engine",
             [lg for _, lg in decode + prefill], [lg for _, lg in want],
             TOL_BF16, check)


def _bytes_in_use(device, key="bytes_in_use"):
    return (device.memory_stats() or {}).get(key, 0)


def _four_chips(args, check, stats):
    import jax
    import numpy as np

    from repro.config import get_arch
    from repro.config.base import ServeConfig
    from repro.dist import make_mesh
    from repro.dist.sharding import param_shardings
    from repro.models import init_params

    cfg = get_arch("starcoder2-15b")
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}, {cfg.param_count() / 1e9:.2f} B "
          f"params (random, seed {args.seed})", flush=True)
    mesh = make_mesh((1, 4), ("data", "model"))
    key = jax.random.PRNGKey(args.seed)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), key)
    shardings = param_shardings(mesh, shapes)
    t0 = time.perf_counter()
    # built straight into its tensor-parallel placement: no chip ever
    # holds the whole model
    params = jax.jit(init_params, static_argnums=0,
                     out_shardings=shardings)(cfg, key)
    jax.block_until_ready(params)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    print(f"init params into their shardings: {time.perf_counter() - t0:.1f}"
          f" s, {weights / 1e9:.2f} GB of weights", flush=True)
    per_chip = [_bytes_in_use(d) for d in jax.devices()]
    print("  bytes in use per chip after init: "
          + ", ".join(f"{b / 1e9:.2f} GB" for b in per_chip)
          + f" (a quarter of the weights is {weights / 4e9:.2f} GB)",
          flush=True)
    check(max(per_chip) <= weights / 3,
          "no chip holds more than a third of the weights")

    rng = np.random.default_rng(args.seed)
    lengths = [64, 100, 256 + 40, 256 + 72, 384, 512]
    prompts = _prompts(rng, cfg.vocab_size, lengths, shared=(2, 3))
    s_pad = -(-(max(lengths) + MAX_NEW) // 64) * 64
    scfg = ServeConfig(max_new_tokens=MAX_NEW, page_size=PAGE,
                       prefix_cache=True)
    eng, _, _ = _phase("mesh (bf16, data=1 x model=4)", cfg, params, scfg,
                       prompts, max_len=2048, check=check, stats=stats,
                       reference=_reference(cfg, None, s_pad), tol=TOL_BF16,
                       mesh=mesh)
    spec = eng.pages.k.sharding.spec
    check(len(spec) > 1 and spec[1] == "model",
          f"KV pool heads sharded over model ({spec})")
    per_chip = [_bytes_in_use(d) for d in jax.devices()]
    print("  bytes in use per chip after serving: "
          + ", ".join(f"{b / 1e9:.2f} GB" for b in per_chip), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: qwen2.5-3b phases A and B on one chip; "
                         "4: starcoder2-15b on a (data=1, model=4) mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke: src/repro not found next to this script; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    from repro.launch.compile_cache import CompileStats, enable_compile_cache

    cache_dir = enable_compile_cache()
    stats = CompileStats()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this script measures nothing off the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__},"
          f" compile cache {cache_dir}", flush=True)

    check = Checks()
    t0 = time.perf_counter()
    if args.chips == 4:
        _four_chips(args, check, stats)
    else:
        _one_chip(args, check, stats)
    peak = [_bytes_in_use(d, "peak_bytes_in_use") for d in devices]
    print(f"smoke timing (cold process, not a benchmark): total "
          f"{time.perf_counter() - t0:.1f} s; backend compile "
          f"{stats.compile_s:.1f} s; persistent cache hits {stats.hits}, "
          f"misses {stats.misses}", flush=True)
    print("peak device memory: " + ", ".join(
        f"{p / 1e9:.2f} GB" for p in peak), flush=True)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              + "; ".join(check.failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
