"""The one table of hardware peaks, keyed by the exact ``device_kind`` JAX
reports.  A device that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture — one
chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e)",
    },
}


class NoAcceleratorError(RuntimeError):
    """No TPU, or fewer chips than the cell asks for: no fallback."""


class UnknownDeviceError(RuntimeError):
    """The trainer ran on a device the peaks table does not list."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.py "
            f"(known: {sorted(PEAKS)}); add its published peaks with their "
            "source before measuring on it") from None
