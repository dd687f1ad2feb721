"""Published peaks of each chip the benchmark may run on, by ``device_kind``.

A chip that is not in the table is an error, never a default: a share of a
peak is only as good as the peak it is taken of.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "https://cloud.google.com/tpu/docs/v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``, as JAX reports it."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]
