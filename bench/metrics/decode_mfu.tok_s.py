"""The decode step's model math at the chip's peaks over its device time."""

from bench.lib import readers


def read(run):
    return readers.mfu(run, "decode")
