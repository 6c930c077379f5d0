#!/usr/bin/env python
"""The paper, end to end, on one GEMV.

1. Assemble the 30-bit ISA program for a tiled integer GEMV (paper Fig. 2/3).
2. Execute it on the cycle-counted tile-controller model — exact result.
3. Run the same GEMV through the TPU engine (bit-plane kernel, interpret
   mode) — identical semantics on the adapted hardware.
4. Report the paper's figures of merit: cycles, execution time @737 MHz,
   and the latency-model comparison against CCB/CoMeFa/SPAR-2/BRAMAC.

    PYTHONPATH=src python examples/gemv_paper_demo.py [--dim 96]
"""

import argparse

import jax.numpy as jnp
import numpy as np

from repro.core.controller import run_gemv
from repro.core.isa import assemble_gemv, roundtrip
from repro.core.latency_model import FIG6_DESIGNS, IMAGINE_FSYS_MHZ
from repro.engine import EnginePlan, pack_linear
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=96)
    args = ap.parse_args()
    dim = args.dim

    rng = np.random.default_rng(0)
    w = rng.integers(-127, 128, size=(dim, dim))
    x = rng.integers(-127, 128, size=(dim,))

    print("== 1. assemble the ISA program ==")
    prog = assemble_gemv(n_elems=12, n_folds=1, out_rows=16)
    words, decoded = roundtrip(prog)
    print(f"instructions={len(prog)} first 4 encoded: "
          + " ".join(f"{wd:08x}" for wd in words[:4]))
    assert decoded == prog

    print("== 2. execute on the tile-controller model ==")
    res = run_gemv(w, x, rows=16, cols=8)
    assert np.array_equal(res.y, w @ x), "FPGA model must be exact"
    us = res.cycles / IMAGINE_FSYS_MHZ
    print(f"exact={np.array_equal(res.y, w @ x)} cycles={res.cycles} "
          f"exec={us:.2f}us @737MHz  y[:4]={res.y[:4]}")

    print("== 3. the same GEMV on the TPU engine (bit-plane kernel) ==")
    # integer weights map exactly into the int8 engine format
    ql = pack_linear(jnp.asarray(w.T, jnp.float32), bits=8)
    plan = EnginePlan(backend="pallas_interpret", bits=8, radix=1)
    y_tpu = plan.apply(ql, jnp.asarray(x, jnp.float32),
                       out_dtype=jnp.float32)
    err = float(np.max(np.abs(np.asarray(y_tpu) - (w @ x))))
    rel = err / max(1.0, float(np.max(np.abs(w @ x))))
    print(f"bit-plane kernel matches: rel_err={rel:.2e}")

    print("== 4. latency-model comparison (paper Fig. 6) ==")
    for name, (fn, f_mhz) in FIG6_DESIGNS.items():
        cyc = fn(dim, 8)
        t = f"{cyc / f_mhz:8.1f}us" if f_mhz else "   (n/a)"
        print(f"  {name:16s} cycles={cyc:>8d} exec={t}")


if __name__ == "__main__":
    main()
