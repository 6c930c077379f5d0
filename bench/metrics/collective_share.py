"""Device: collective time with no other op beside it over device-busy time, in %."""

from bench.yardstick import readers


def read(run):
    return readers.collective_pct(run)
