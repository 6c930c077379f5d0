"""90th percentile of time to first token over the requests due in the window, from their due time, in ms."""

from bench.yardstick import readers


def read(run):
    return readers.ttft_ms(run, 90)
