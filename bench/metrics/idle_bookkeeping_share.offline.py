"""Scheduler and page tables: device-idle time inside serve.schedule, serve.tables, serve.lane_update and serve.retire, over the traced window, in %."""

from bench.yardstick import idle


def read(run):
    return idle.bookkeeping_pct(run)
