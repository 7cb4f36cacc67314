"""Host time of the packed-sparse edge extraction (`packed_pair_edges`) in
a `score()` call: the `engine.edges` span less the `engine.*` spans nested
in it (its copies and waits), mean ms per call over the traced window.
Nothing to read where no call takes the packed-sparse path."""
from harness.stages import per_call_ms


def read(ctx):
    return per_call_ms(ctx, ["engine.edges"])
