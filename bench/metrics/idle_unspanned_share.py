"""Caller of ServeEngine.step: device-idle time outside every serve.* span, over the traced window, in %."""

from bench.yardstick import idle


def read(run):
    return idle.unspanned_run_pct(run)
