"""Host time of packing pairs into tiles (or size buckets) in a `score()`
call: the `engine.pack` span less the `engine.*` spans nested in it (edge
extraction and copies), mean ms per call over the traced window."""
from harness.stages import per_call_ms


def read(ctx):
    return per_call_ms(ctx, ["engine.pack"])
