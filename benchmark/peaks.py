"""Published peaks of the chips this benchmark may run on, keyed by
`jax.devices()[0].device_kind`. A device that is not here is an error, not
a default: a share of a peak needs the peak.

Source: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            "with its source to benchmark/peaks.py"
        )
    return PEAKS[device_kind]
