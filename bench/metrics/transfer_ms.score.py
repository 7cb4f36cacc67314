"""Host time of the copies between host and device in a `score()` call:
the `engine.to_device` and `engine.to_host` spans, each less the spans
nested in it, mean ms per call over the traced window."""
from harness.stages import per_call_ms


def read(ctx):
    return per_call_ms(ctx, ["engine.to_device", "engine.to_host"])
