"""Kernel: attn_decode_roofline for the offline cell, in %."""

from bench.yardstick import readers


def read(run):
    return readers.attn_decode_roofline_pct(run)
