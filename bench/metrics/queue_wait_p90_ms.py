"""Scheduler: due time to admission, 90th percentile over the requests due in the window, in ms."""

from bench.yardstick import readers


def read(run):
    return readers.queue_wait_ms(run, 90)
