"""Output tokens emitted in the window over the window's seconds."""

from bench.yardstick import readers


def read(run):
    return readers.output_tok_s(run)
