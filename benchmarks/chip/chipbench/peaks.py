"""Published peaks of one chip, keyed by ``device.device_kind``.

TPU v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16,
819 GB/s of HBM bandwidth, 16 GB of HBM.  A kind that is not in the table
is an error, never a default."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,  # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
