"""Published per-chip peaks, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, not a default.
"""

from __future__ import annotations

#: device_kind -> peaks of one chip. Source: Google Cloud documentation,
#: "TPU v5e" (system architecture): 197 TFLOP/s bf16, 394 TOP/s int8,
#: 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py with "
                       f"their source") from None
