"""Padding share of the packed tiles: 1 - mean occupancy of both sides
(`engine.last_pack_stats`), in percent, over the window's calls."""
import numpy as np


def read(ctx):
    occ = ctx.get("occupancy", [])
    return 100.0 * (1.0 - float(np.mean(occ))) if occ else None
