"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip. A kind that is
not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
