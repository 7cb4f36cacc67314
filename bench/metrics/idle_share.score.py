"""Share of the traced window in which no operation ran on the device, in
percent (1 - busy / window from the profiler trace)."""
from harness.readers import idle_share


def read(ctx):
    return idle_share(ctx)
