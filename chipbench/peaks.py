"""Published peaks of each chip the benchmark may run on, keyed by
``jax.devices()[0].device_kind``.  A device that is not listed is an
error, never a default.

TPU v5e (``device_kind`` "TPU v5 lite"): Google Cloud documentation,
"TPU v5e" system architecture page: 197 TFLOP/s bf16, 394 TOP/s int8,
16 GiB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud TPU documentation, 'TPU v5e'",
    },
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add a row with its source to chipbench/peaks.py)"
        ) from None


def least_time_s(flops: float, bytes_moved: float, peaks: dict,
                 flops_key: str = "bf16_flops") -> tuple[float, str]:
    """The roofline's least time for ``flops`` operations that move
    ``bytes_moved`` bytes, and which of the two bounds it."""
    t_c = flops / peaks[flops_key]
    t_m = bytes_moved / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
