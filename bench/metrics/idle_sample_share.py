"""Sampling: device-idle time inside serve.sample, over the traced window, in %."""

from bench.yardstick import idle


def read(run):
    return idle.sample_pct(run)
