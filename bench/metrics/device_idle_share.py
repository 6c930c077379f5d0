"""Device: share of the traced window in which no op ran, averaged over the chips, in %."""

from bench.yardstick import readers


def read(run):
    return readers.device_idle_pct(run)
