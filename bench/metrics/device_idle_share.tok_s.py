"""Share of the traced window in which no operation ran on the chip."""

from bench.lib import readers


def read(run):
    return readers.idle_share(run)
