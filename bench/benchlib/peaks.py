"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error."""
from __future__ import annotations

PEAKS: dict[str, dict] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(SystemExit):
    """The chip is not in the peaks table."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in the peaks table "
            f"(bench/benchlib/peaks.py); known: {sorted(PEAKS)}") from None
