#!/usr/bin/env python3
"""Benchmark entry point: one cell of ``BENCHMARK.json``, one seed.

    python bench/run.py --workload qwen2.5-3b.chat --seed 7 --seconds 40 \\
        --trace 0

Runs on the machine it is started on and refuses to run without as many
TPU chips as the cell asks for.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared against the float32 reference beside its limit.  The
same checks are the last lines of standard error.  Limits are set with
``bench/limits.py``, not here.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        _log("bench/run.py: the program (src/repro) is not in this "
             "checkout; nothing to measure")
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    from bench import harness

    cell = harness.load_cell(args.workload)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        _log(f"bench/run.py: {cell.name} needs {cell.chips} TPU chip(s); "
             f"JAX sees {len(devices)} {devices[0].platform} device(s)")
        return 2
    _log(f"device: {devices[0].device_kind} x{len(devices)} "
         f"(platform {devices[0].platform}), jax {jax.__version__}, "
         f"compile cache {cache}")

    compiles = harness.CompileCounter()
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, compiles, log=_log)
    harness.emit_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
