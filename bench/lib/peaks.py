"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` that JAX reports. A chip missing here is an error: the
benchmark never guesses a peak."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, 'TPU v5e' (system architecture):"
                  " 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"add them to bench/lib/peaks.py (known: {sorted(PEAKS)})"
        ) from None
