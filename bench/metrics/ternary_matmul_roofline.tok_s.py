"""Roofline share of the act-quant ternary matmul kernel in the decode step."""

from bench.lib import readers


def read(run):
    return readers.roofline(run, "ternary_matmul", "decode")
