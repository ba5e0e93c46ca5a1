"""Device time per call of the jitted decode-step program, from the trace."""

from bench.lib import readers


def read(run):
    return readers.program_ms(run, "decode")
