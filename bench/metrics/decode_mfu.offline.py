"""Model step: decode_mfu for the offline cell, in %."""

from bench.yardstick import readers


def read(run):
    return readers.decode_mfu_pct(run)
