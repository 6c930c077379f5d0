"""Engine: window seconds per ServeEngine.step() call, in ms."""

from bench.yardstick import readers


def read(run):
    return readers.engine_step_ms(run)
