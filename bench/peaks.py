"""Published peaks per chip, keyed by `jax.Device.device_kind`.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). A device kind that is not
in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}

SOURCE = 'Google Cloud documentation, "TPU v5e"'


class UnknownDeviceKind(KeyError):
    """The chip is not in the peaks table: no share of a peak can be
    computed for it."""


def peaks_for(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"device kind {device_kind!r} is not in bench/peaks.py "
            f"(known: {sorted(PEAKS)})"
        ) from None


def roofline_s(flops: float, nbytes: float, peak: dict,
               flops_key: str = "bf16_flops") -> float:
    """Least time the chip could take for `flops` operations moving
    `nbytes` bytes: the larger of the compute and the memory bound."""
    return max(flops / peak[flops_key], nbytes / peak["hbm_bytes_per_s"])
