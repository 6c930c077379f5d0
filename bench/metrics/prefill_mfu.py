"""Model step: the prefill calls' least time on the binding resource over the device time inside serve.prefill_chunk, in %."""

from bench.yardstick import readers


def read(run):
    return readers.prefill_mfu_pct(run)
