"""The chip the run is on, and what it can do at best.

PEAKS is keyed by JAX's ``device_kind``. Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bfloat16, 819 GB/s HBM bandwidth,
16 GB HBM per chip. A kind that is not in the table is an error, never a
default.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class NoChip(RuntimeError):
    """No TPU, too few chips, or a kind with no peaks."""


@dataclass(frozen=True)
class Peaks:
    flops: float              # bf16 FLOP/s of one chip
    bytes_per_s: float        # HBM bytes/s of one chip


def peaks_for(kind: str) -> Peaks:
    try:
        p = PEAKS[kind]
    except KeyError:
        raise NoChip(f"no peaks known for device kind {kind!r}") from None
    return Peaks(p["flops_bf16"], p["hbm_bytes_per_s"])


def require_chips(chips: int) -> List:
    """JAX's devices, which must be `chips` TPUs or more, of a known kind.

    Refuses REPRO_FORCE_REF / REPRO_FORCE_PALLAS, which would take the
    kernels off the chip (the same guard as `chip_smoke.py`)."""
    for var in ("REPRO_FORCE_REF", "REPRO_FORCE_PALLAS"):
        if os.environ.get(var):
            raise NoChip(f"{var} is set; it would keep the kernels off "
                         "the chip")
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU found: JAX's first device is "
                     f"{dev.platform!r}; the benchmark does not fall back")
    if len(devices) < chips:
        raise NoChip(f"{len(devices)} chips found, the cell needs {chips}")
    peaks_for(dev.device_kind)
    return devices[:chips]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def describe(devices, all_count: int) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": all_count,
            "memory_peak_bytes": memory_peak_bytes(devices)}
