"""Scheduler: mean share of the lanes decoding per decode step, in %."""

from bench.yardstick import readers


def read(run):
    return readers.lane_occupancy_pct(run)
