"""Device: device_idle_share for the offline cell, in %."""

from bench.yardstick import readers


def read(run):
    return readers.device_idle_pct(run)
