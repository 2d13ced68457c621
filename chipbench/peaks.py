"""Published peak rates per chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 200e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak rates for device_kind={device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
