"""Useful model operations per second of the window over the chip's bf16
peak, in percent."""
from harness.readers import share_of_peak


def read(ctx):
    return share_of_peak(ctx["useful_flops"], ctx["window_s"], ctx)
