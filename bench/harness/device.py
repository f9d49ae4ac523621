"""The chip: what JAX reports about it, and its published peaks.

The peaks are the benchmark's own copy, so no change to the program can move
the yardstick.  A ``device_kind`` that is not in the table is an error, never
a default.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def device_info(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_tpu(devices, chips: int) -> None:
    """Raise ``NoAccelerator`` unless ``devices`` holds ``chips`` TPUs."""
    info = device_info(devices)
    if info["platform"] != "tpu":
        raise NoAccelerator(
            f"the first device is {info['platform']!r}, not a TPU; "
            "the benchmark runs only on the chip"
        )
    if info["count"] < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees {info['count']}")


def memory_peak_bytes(devices) -> int | None:
    """The fullest device's peak of bytes in use plus its peak of bytes
    reserved, where the backend says.  XLA:TPU keeps a program's temporaries
    in the reserved bytes, outside ``peak_bytes_in_use``; both peaks are
    process-wide, so this reads everything up to the moment it is called."""
    peaks_seen = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks_seen.append(int(stats["peak_bytes_in_use"])
                              + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks_seen) if peaks_seen else None
