"""Published peaks of each accelerator, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
The chip publishes no peak for the 64-bit integer work of the planning
scan, so the scan's roofline is bounded by bytes alone.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "int8_op_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
    },
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device missing here is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}") from None


def scan_bytes(n: int, links: int, slots: int) -> int:
    """Bytes one planning scan call must move at least: the gathered
    ``[n, L, W]`` window of 8-byte slot fractions plus three ``[n, W]``
    rows (usable seconds in, cumulative bytes and bandwidth out)."""
    return 8 * n * slots * (links + 3)
