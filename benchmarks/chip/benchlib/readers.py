"""Pieces shared by the per-layer metric readers in `metrics/`.

A reader is `read(ctx) -> float | None`; ctx holds the counters of the
cell's driver (`benchlib/drivers/`), the reduced trace (`reduced`), the
chip's `peaks`, and the cell's `config` and `traffic`. A reader that
finds nothing to read returns None.
"""
from __future__ import annotations

from typing import Optional

from .tracered import module_stats


def idle_pct(ctx: dict) -> Optional[float]:
    red = ctx.get("reduced")
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * red.idle_share


def roofline_pct(flops: float, nbytes: float, calls: int, seconds: float,
                 peaks) -> Optional[float]:
    """Least time the chip allows for `calls` such calls, over the time
    they took: the larger of FLOPs over peak FLOP/s and bytes over peak
    bandwidth bounds it."""
    if not calls or seconds <= 0:
        return None
    least = max(flops / peaks.flops, nbytes / peaks.bytes_per_s)
    return 100.0 * least * calls / seconds


def program_time(ctx: dict, fragment: str):
    """(count, seconds) of the device programs named with `fragment`."""
    red = ctx.get("reduced")
    if red is None:
        return 0, 0.0
    return module_stats(red, fragment)
