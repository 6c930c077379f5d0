"""Kernel: modelled work of paged_attention_prefill at the lanes' real contexts over its summed device time, in %."""

from bench.yardstick import readers


def read(run):
    return readers.attn_prefill_roofline_pct(run)
