#!/usr/bin/env python3
"""Readings that set a cell's ``mean_gap_limit``: for each seed, one run
of the cell at its own load, then the program's number and the
control's on the same served positions.

    python bench/limits.py --workload qwen2.5-3b.chat --seconds 51 \\
        --seeds 11,12,13 --control w8kv8

Seeds run one after another in one process, so set-up is paid once and
no timing is printed: this is not a benchmark run.  For each seed it
prints one JSON line with the program's checks, its verdict, and with
``--control`` the control's checks and verdict (which has to come out
not correct).  The limit is then written into the cell's traffic file
by hand.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", choices=("w8kv8",), default=None)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax

    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("bench/limits.py: needs the cell's TPU chips", file=sys.stderr)
        return 2
    compiles = harness.CompileCounter()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(cell, seed, args.seconds, False,
                          time.perf_counter(), compiles,
                          control=args.control, log=log)
        line = {"seed": seed, "correct": out["correct"],
                "checks": out["checks"]}
        if "control" in out:
            line["control"] = out["control"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
