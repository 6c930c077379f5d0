"""IMAGine engine end-to-end: the executable ISA/controller model runs an
exact integer GEMV and its cycle count feeds the latency model; the same
GEMV through the TPU engine (bit-plane path) is validated for equality.

Derived columns give the paper's own figures of merit: cycles, execution
time at 737 MHz, and effective MAC/s for the FPGA overlay, plus the memory
roofline time for the equivalent TPU decode GEMV."""

import numpy as np

from repro.core.controller import CycleModel, run_gemv
from repro.core.latency_model import IMAGINE_FSYS_MHZ, U55
from repro.roofline.analysis import V5E, peaks


def run():
    rows = []
    rng = np.random.default_rng(0)
    for dim, rows_pe, cols_pe in ((64, 16, 8), (128, 32, 8), (240, 16, 16)):
        w = rng.integers(-127, 128, size=(dim, dim))
        x = rng.integers(-127, 128, size=(dim,))
        res = run_gemv(w, x, rows=rows_pe, cols=cols_pe)
        exact = bool(np.array_equal(res.y, w @ x))
        us = res.cycles / IMAGINE_FSYS_MHZ
        macs = dim * dim
        rows.append((
            f"engine.isa_gemv.d{dim}", round(us, 2),
            f"cycles={res.cycles} instrs={res.instrs} exact={exact}"
            f" mac_per_cycle={macs / res.cycles:.2f}"))

    # device-level: full-U55 GEMV at max occupancy vs one v5e chip's HBM
    # roofline for the same int8 weight matrix (the TPU adaptation)
    cm = CycleModel(precision=8)
    dim = 1967  # max resident square GEMV on U55 (tile_array capacity)
    pes = U55.max_pes
    elems = -(-dim * dim // pes)
    fpga_cycles = elems * cm.mac() + cm.accum(32) + dim
    fpga_us = fpga_cycles / IMAGINE_FSYS_MHZ
    tpu_us = (dim * dim * 1) / peaks(V5E)["hbm_bw"] * 1e6  # int8 weights, 1B/w
    rows.append(("engine.u55_vs_v5e_gemv.d1967", round(fpga_us, 1),
                 f"fpga_cycles={fpga_cycles}"
                 f" v5e_hbm_bound_us={tpu_us:.2f}"
                 f" note=same_weight_stationary_int8_gemv"))
    return rows
