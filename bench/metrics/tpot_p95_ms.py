"""95th percentile over all gaps between consecutive output tokens emitted in the window, in ms."""

from bench.yardstick import readers


def read(run):
    return readers.tpot_ms(run, 95)
