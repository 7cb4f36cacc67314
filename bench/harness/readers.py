"""Arithmetic the per-layer metric readers share. Each reader returns None
where its run holds nothing to read, and never 0 for a share of a peak or
a roofline."""

from __future__ import annotations

import numpy as np


def mean_ms(values) -> float | None:
    return float(np.mean(values)) * 1e3 if len(values) else None


def idle_share(ctx) -> float | None:
    red = ctx.get("trace")
    if red is None or red.window_s <= 0 or red.busy_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)


def share_of_peak(flops: float, seconds: float, ctx) -> float | None:
    peaks = ctx.get("peaks")
    if not peaks or flops <= 0 or seconds <= 0:
        return None
    return 100.0 * flops / seconds / peaks["bf16_flops"]


def roofline(flops: float, nbytes: float, device_s: float,
             ctx) -> float | None:
    """Least time the chip needs for the work (the larger of operations over
    peak and bytes over bandwidth) as a share of the device time it took."""
    peaks = ctx.get("peaks")
    if not peaks or flops <= 0 or device_s <= 0:
        return None
    least = max(flops / peaks["bf16_flops"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / device_s
