"""Host time in a `score()` call under no stage of its own: the
`engine.score` span less the `engine.*` spans nested in it, mean ms per
call over the traced window."""
from harness.stages import per_call_ms


def read(ctx):
    return per_call_ms(ctx, ["engine.score"])
