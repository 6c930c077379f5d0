#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one process, one engine, a stage
per rate in rising order, each ``--stage-seconds`` long.

    python bench/sweep.py --workload qwen2.5-3b.chat --seed 3 \\
        --rates 0.2,0.3,0.4,0.5 --stage-seconds 60

Stages run back to back with no drain between them, so a rate the engine
cannot hold leaves a queue that grows through its stage.  For each stage
it prints the arrivals, the requests waiting for admission at the stage's
start and end, and TTFT (from the due time) and queue-wait percentiles
of the requests due in it.  The knee is the highest rate whose queue
does not grow.  Not part of a benchmark run: the rate it finds is written
into the cell's traffic file by hand.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--stage-seconds", type=float, default=60.0)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax

    from bench import harness
    from bench.yardstick import traffic
    from bench.yardstick.stats import percentile
    from repro.dist import make_mesh
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("bench/sweep.py: needs the cell's TPU chips", file=sys.stderr)
        return 2
    mesh = (make_mesh(tuple(cell.cfg["mesh"]), ("data", "model"))
            if cell.chips > 1 else None)
    params = harness.make_params(cell, args.seed, mesh)
    eng = harness.build_engine(cell, params, mesh, trace=False, control=None)
    del params
    harness.warm_up(eng, cell, args.seed)
    print(f"set-up {time.perf_counter() - T_START:.1f} s", flush=True)
    tr = harness.Tracker(eng)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_per_s"] = rate
        specs = traffic.Generator(mix, args.seed + k, cell.dims.vocab
                                  ).open_loop(args.stage_seconds)
        start = tr.clock()
        q0 = tr.queued()
        recs, i = [], 0
        while tr.clock() < start + args.stage_seconds:
            now = tr.clock()
            while i < len(specs) and start + specs[i].due_s <= now:
                recs.append(tr.submit(specs[i], start + specs[i].due_s))
                i += 1
            if eng.has_work():
                tr.step()
            else:
                time.sleep(0.005)
        end = tr.clock()
        ttft = [(r.tokens[0] if r.tokens else end) - r.due for r in recs]
        wait = [(r.admitted if r.admitted is not None else end) - r.due
                for r in recs]
        row = {"rate_per_s": rate, "arrivals": len(recs),
               "queued_at_start": q0, "queued_at_end": tr.queued(),
               "first_token_by_end": sum(bool(r.tokens) for r in recs),
               "ttft_p50_s": percentile(ttft, 50),
               "ttft_p90_s": percentile(ttft, 90),
               "queue_wait_p90_s": percentile(wait, 90),
               "decoding_at_end": tr.decoding()}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
