"""Published peaks per device, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip gives 197 TFLOP/s in bf16, 393 TOP/s in int8, and 16 GB of HBM at
819 GB/s. The step computes in float32 at ``Precision.HIGHEST``, which the
MXU runs as six bf16 passes; shares are still taken of the bf16 peak, the
most the chip can do. A device that is not in the table is an error.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
