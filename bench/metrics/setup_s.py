"""Set-up seconds: process start to the window's opening (imports, weights, engine, compilation or cache reads, warm-up, the traffic's lead or ramp)."""

from bench.yardstick import readers


def read(run):
    return run.setup_s
