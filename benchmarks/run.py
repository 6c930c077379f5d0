# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: every paper table/figure regenerated, plus kernel
micro-benchmarks and the TPU roofline summary.

  python -m benchmarks.run            # all benches, CSV on stdout
  python -m benchmarks.run fig6       # one bench
"""

import sys


def main() -> None:
    from benchmarks import (
        attn_bench,
        chaos_bench,
        costs_bench,
        engine_model,
        fig4_scaling,
        fig6_latency,
        kernel_bench,
        load_bench,
        obs_bench,
        prefix_bench,
        roofline_summary,
        serve_bench,
        table1_fmax,
        table3_tile,
        table5_freq,
    )

    benches = {
        "table1": table1_fmax.run,
        "table3": table3_tile.run,
        "fig4": fig4_scaling.run,
        "table5": table5_freq.run,
        "fig6": fig6_latency.run,
        "kernels": kernel_bench.run,
        "engine": engine_model.run,
        "roofline": roofline_summary.run,
        "serve": serve_bench.run,
        "attn": attn_bench.run,
        "prefix": prefix_bench.run,
        "load": load_bench.run,
        "obs": obs_bench.run,
        "chaos": chaos_bench.run,
        "costs": costs_bench.run,
    }
    from benchmarks.common import bench_env
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    env = bench_env()
    print(f"# device_kind={env['device_kind']}  "
          f"interpret_mode={env['interpret_mode']}")
    picked = sys.argv[1:] or list(benches)
    print("name,us_per_call,derived")
    for name in picked:
        if name not in benches:
            raise SystemExit(f"unknown bench {name!r}; have {sorted(benches)}")
        for row in benches[name]():
            print(",".join(str(v) for v in row))


if __name__ == "__main__":
    main()
