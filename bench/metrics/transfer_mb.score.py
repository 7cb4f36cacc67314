"""Bytes a `score()` call copies between host and device, in MB (1e6
bytes) per call over the traced window: the program counts each copy at
its site and records the count on the `engine.to_device`/`engine.to_host`
span of that site (stat `bytes`)."""
from harness.stages import per_call_mb


def read(ctx):
    return per_call_mb(ctx, ["engine.to_device", "engine.to_host"])
