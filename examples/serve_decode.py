#!/usr/bin/env python
"""End-to-end serving driver (the paper's kind of workload: GEMV-bound
decode).  Trains a small LM briefly so weights are meaningful, then serves
a stream of batched requests — through the legacy fixed-slot engine, the
paged-KV continuous-batching engine (batched chunked prefill + block-table
decode), and the fully-quantized IMAGine mode (int8 bit-plane weights +
int8 KV pages) — and reports the weight- and KV-byte reductions plus the
greedy-token agreement across modes.

    PYTHONPATH=src python examples/serve_decode.py [--tokens 24] [--reqs 6]
"""

import argparse
import dataclasses

import jax
import jax.numpy as jnp

import repro.obs as obs
from repro.config import get_reduced
from repro.config.base import EngineConfig, ServeConfig, TrainConfig
from repro.data import DataPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serve import ServeEngine, ServeFrontend
from repro.train import Trainer


def tree_bytes(t):
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(t)
               if hasattr(l, "dtype"))


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=12)
    ap.add_argument("--reqs", type=int, default=6)
    ap.add_argument("--train-steps", type=int, default=20)
    args = ap.parse_args()

    # observability on for the whole driver: every engine below carries a
    # live telemetry (metrics + Chrome trace); docs/observability.md
    obs.enable()

    cfg = dataclasses.replace(get_reduced("qwen2.5-3b"), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))

    print(f"== train {args.train_steps} steps so the LM is non-random ==")
    tcfg = TrainConfig(lr=1e-3, total_steps=args.train_steps, warmup_steps=2)
    pipe = DataPipeline(cfg, batch=4, seq_len=48, seed=0)
    tr = Trainer(cfg, tcfg, params, pipe)
    hist = tr.run(args.train_steps)["loss"]
    print(f"loss {hist[0]:.3f} -> {hist[-1]:.3f}")
    params = tr.params

    # a shared "system prompt" prefix + per-request tails: the kind of
    # stream the prefix cache collapses to suffix-only prefill
    system = [(3 * j + 1) % cfg.vocab_size for j in range(16)]
    prompts = [system + [(7 * i + j) % cfg.vocab_size
                         for j in range(3 + i % 4)]
               for i in range(args.reqs)]

    results = {}
    for label, mode, engine, prefix_cache in (
        ("slots-dense", "slots", EngineConfig(), False),
        ("paged-dense", "paged", EngineConfig(), False),
        ("paged-prefix-cache", "paged", EngineConfig(), True),
        ("paged-kv8", "paged",
         EngineConfig(kv_bits=8, backend="reference"), False),
        ("paged-imagine-int8", "paged",
         EngineConfig(weight_bits=8, kv_bits=8, backend="reference"), False),
    ):
        eng = ServeEngine(
            cfg, params,
            ServeConfig(max_new_tokens=args.tokens, engine=engine,
                        page_size=8, prefill_chunk=8),
            n_slots=4, max_len=64, mode=mode, prefix_cache=prefix_cache)
        for p in prompts:
            eng.submit(p)
        done = eng.run()
        # no hand-rolled perf_counter math: the engine's own telemetry
        # already timed every step
        m = eng.metrics()
        dt = m["obs"]["metrics"]["histograms"]["serve_step_s"]["sum"]
        wbytes = tree_bytes(eng.params)
        kvbytes = (eng.pages.nbytes() if mode == "paged"
                   else tree_bytes(eng.cache))
        results[label] = done
        extra = (f", preemptions={m['preemptions']}" if mode == "paged"
                 else "")
        if eng.prefix_cache is not None:
            st = m["prefix"]
            extra += (f", prefill computed {m['prefill_computed']} tokens "
                      f"({st['hit_tokens']} from cache, "
                      f"{st['cow_forks']} COW forks)")
        print(f"== {label}: {len(done)} requests, {dt:.1f}s, "
              f"weights={wbytes/1e6:.1f}MB, kv={kvbytes/1e6:.2f}MB{extra} ==")
        for r in sorted(done, key=lambda r: r.rid)[:3]:
            hit = (f" ({r.cached_tokens} prompt tokens from cache)"
                   if r.cached_tokens else "")
            print(f"  req{r.rid}: prompt={r.prompt} -> {r.output}{hit}")

    base = {r.rid: r.output for r in results["slots-dense"]}
    for label in ("paged-dense", "paged-prefix-cache", "paged-kv8",
                  "paged-imagine-int8"):
        agree = sum(
            t1 == t2
            for r in results[label]
            for t1, t2 in zip(base[r.rid], r.output))
        total = sum(len(r.output) for r in results[label])
        print(f"{label}: greedy agreement with slots-dense = "
              f"{agree}/{total}")

    # --- streaming front-end: tokens as they are produced, SLA-aware ---
    # the budget scheduler interleaves chunked prefill with decode under
    # a per-step token budget; priorities get weighted fair shares; the
    # bounded queue sheds overload with a reason instead of queueing it
    print("\n== streaming front-end (budget scheduler, bounded queue) ==")
    eng = ServeEngine(
        cfg, params,
        ServeConfig(max_new_tokens=args.tokens, engine=EngineConfig(),
                    page_size=8, prefill_chunk=8,
                    sched="budget", step_tokens=12, max_queue=3),
        n_slots=2, max_len=64, mode="paged", prefix_cache=True)
    fe = ServeFrontend(eng)
    streams = [
        fe.submit(prompts[0], priority="interactive", tenant="app"),
        fe.submit(prompts[1], priority="batch", tenant="etl"),
        fe.submit(prompts[2], priority="default", deadline_s=30.0),
    ]
    # the admission queue (max_queue=3) is full -> the 4th sheds at the
    # door with a reason instead of growing the tail unboundedly
    shed = fe.submit(prompts[3])
    print(f"  shed stream: state={shed.state!r} "
          f"reason={shed.shed_reason!r} (no exception on the hot path)")
    # pull tokens incrementally, round-robin — each next() drives the
    # shared engine, so all lanes advance together
    for s in streams:
        first = next(s)
        print(f"  stream rid={s.rid} [{s.req.priority}] first token "
              f"{first} after {1e3 * s.ttft():.0f}ms (state={s.state})")
    for s in streams:
        s.result()  # drain the rest
    fe.drain()
    print(f"  done: {[len(s.tokens) for s in fe.streams]} tokens/stream, "
          f"{fe.shed_count} shed, {fe.timeout_count} timed out")

    # --- the observability surface this run produced ---
    snap = eng.metrics()
    o = snap["obs"]["metrics"]
    ttft = o["histograms"]["serve_ttft_s"]
    print("\n== ServeEngine.metrics() snapshot (streaming engine) ==")
    print(f"  steps={snap['obs']['steps']}  "
          f"request_states={snap['obs']['request_states']}")
    for k in ("serve_requests_submitted_total",
              "serve_tokens_generated_total",
              "serve_prefill_tokens_total",
              'serve_requests_shed_total{reason="queue_full"}',
              "prefix_cache_hits_total"):
        if k in o["counters"]:
            print(f"  {k} = {o['counters'][k]}")
    print(f"  serve_ttft_s: count={ttft['count']} p50={ttft['p50']:.4f}s "
          f"max={ttft['max']:.4f}s")
    trace_path = eng.obs.export_chrome_trace("serve_trace.json")
    print(f"  Chrome trace -> {trace_path} "
          f"(load it at https://ui.perfetto.dev)")
    obs.disable()


if __name__ == "__main__":
    main()
