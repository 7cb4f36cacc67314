"""Host time blocked on device results in a `score()` call before they are
copied back: the `engine.wait` spans (which nest no other), mean ms per
call over the traced window."""
from harness.stages import per_call_ms


def read(ctx):
    return per_call_ms(ctx, ["engine.wait"])
