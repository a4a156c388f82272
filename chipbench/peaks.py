"""Published peaks of one chip, keyed by JAX's `device_kind`.

TPU v5e (JAX reports it as "TPU v5 lite"): 197 TFLOP/s in bf16 and 819 GB/s
of HBM bandwidth per chip (Google Cloud documentation, "TPU v5e").
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
