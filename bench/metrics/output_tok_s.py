"""Output tokens emitted in the window, in flight at its edges included,
over the window's length."""

from bench.lib import readers


def read(run):
    return readers.rate(run, "output")
