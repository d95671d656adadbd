"""Published peaks of the chips the benchmark knows, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197 TFLOP/s
in bf16, 819 GB/s of HBM bandwidth, 16 GB of HBM per chip. A device that is
not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_of(device_kind: str) -> dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device kind {device_kind!r} is not in the benchmark's peaks "
            f"table (known: {sorted(PEAKS)})"
        )
    return PEAKS[device_kind]
