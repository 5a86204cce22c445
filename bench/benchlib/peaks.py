"""The one table of peaks, keyed by ``device_kind`` as JAX reports it.

An unknown kind is an error, never a default: a wrong peak rescales every
roofline and every MFU in silence.
"""

from __future__ import annotations

# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r}; add it to "
            "bench/benchlib/peaks.py with its source")
    return PEAKS[device_kind]
