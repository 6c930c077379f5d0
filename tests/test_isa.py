"""ISA encode/decode + controller FSM: exact GEMV and cycle accounting."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.controller import CycleModel, GemvTileController, run_gemv
from repro.core.isa import (
    Instr,
    Op,
    SINGLE_CYCLE,
    assemble_gemv,
    decode,
    roundtrip,
)


@given(
    op=st.sampled_from(list(Op)),
    rd=st.integers(0, 63),
    rs1=st.integers(0, 63),
    rs2=st.integers(0, 63),
    imm=st.integers(0, 127),
)
@settings(max_examples=200, deadline=None)
def test_encode_decode_roundtrip(op, rd, rs1, rs2, imm):
    i = Instr(op, rd, rs1, rs2, imm)
    w = i.encode()
    assert 0 <= w < (1 << 30)
    assert decode(w) == i


def test_encode_rejects_out_of_range():
    with pytest.raises(ValueError):
        Instr(Op.ADD, rd=64).encode()
    with pytest.raises(ValueError):
        Instr(Op.ADD, imm=128).encode()
    with pytest.raises(ValueError):
        decode(1 << 30)


def test_program_roundtrip():
    prog = assemble_gemv(n_elems=5, n_folds=2, out_rows=4)
    words, decoded = roundtrip(prog)
    assert decoded == prog
    assert all(0 <= w < 2**30 for w in words)


@given(
    m=st.integers(1, 40),
    k=st.integers(1, 64),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_controller_gemv_exact(m, k, seed):
    rng = np.random.default_rng(seed)
    w = rng.integers(-127, 128, size=(m, k))
    x = rng.integers(-127, 128, size=(k,))
    res = run_gemv(w, x, rows=16, cols=8)
    np.testing.assert_array_equal(res.y, w @ x)
    assert res.cycles > 0


def test_cycle_accounting_matches_model():
    """Controller cycle count == analytic instruction-cost sum."""
    rng = np.random.default_rng(0)
    w = rng.integers(-8, 8, size=(8, 16))
    x = rng.integers(-8, 8, size=(16,))
    res = run_gemv(w, x, rows=8, cols=4)
    cm = CycleModel()
    expect = 0
    for op_, count in res.ctrl.instr_count.items():
        cost = cm.for_instr(Instr(op_), n_cols=4)
        expect += cost * count
    # plus the data-load cycles charged by load_weights/load_activations
    elems = 4
    expect += elems  # activations
    expect += elems  # weights (1 fold)
    assert res.cycles == expect


def test_single_vs_multicycle_drivers():
    cm = CycleModel(precision=8)
    for op_ in SINGLE_CYCLE:
        assert cm.for_instr(Instr(op_), 4) == 1
    assert cm.for_instr(Instr(Op.MULT), 4) > 8
    assert cm.for_instr(Instr(Op.MAC), 4) > cm.for_instr(Instr(Op.MULT), 4)


def test_radix4_halves_mult_passes():
    """The slice4 variant (radix-4 Booth) halves multiply latency."""
    r2 = CycleModel(precision=8, radix_bits=1)
    r4 = CycleModel(precision=8, radix_bits=2)
    assert r4.mult() - r4.issue == (r2.mult() - r2.issue) // 2


def test_halt_stops_execution():
    ctrl = GemvTileController(2, 2)
    ctrl.execute([Instr(Op.HALT)])
    with pytest.raises(RuntimeError):
        ctrl.execute([Instr(Op.NOP)])
