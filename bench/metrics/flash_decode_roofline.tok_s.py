"""Roofline share of the flash-decode kernel; bytes are the KV of each
decoding slot's valid prefix."""

from bench.lib import readers


def read(run):
    return readers.roofline(run, "flash_decode", "decode")
