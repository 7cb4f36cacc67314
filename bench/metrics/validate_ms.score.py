"""Host time of input validation (`validate_pairs`) in a `score()` call:
the `engine.validate` span less the `engine.*` spans nested in it, mean ms
per call over the traced window."""
from harness.stages import per_call_ms


def read(ctx):
    return per_call_ms(ctx, ["engine.validate"])
