"""Model step: the decode steps' least time on the binding resource over the device time inside serve.decode_step, in %."""

from bench.yardstick import readers


def read(run):
    return readers.decode_mfu_pct(run)
