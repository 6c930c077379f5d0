"""Kernel: modelled work of paged_attention_decode at the lanes' real contexts over its summed device time, in %."""

from bench.yardstick import readers


def read(run):
    return readers.attn_decode_roofline_pct(run)
