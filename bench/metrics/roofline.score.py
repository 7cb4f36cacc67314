"""Least time for the window's useful scoring work over the summed device
time of all device operations in the traced window, in percent."""
from harness.readers import roofline


def read(ctx):
    red = ctx.get("trace")
    if red is None:
        return None
    return roofline(ctx["useful_flops"], ctx["useful_bytes"],
                    red.device_seconds(), ctx)
