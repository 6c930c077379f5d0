"""Engine step: device-idle time inside serve.logits_to_host, the host copy of the logits, over the traced window, in %."""

from bench.yardstick import idle


def read(run):
    return idle.logits_pct(run)
