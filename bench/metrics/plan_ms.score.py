"""Host time of a `score()` call outside its executors: validation,
planning and the engine's own bookkeeping (the call's wall time minus the
wall seconds its trace records give its work items), mean ms per call."""
from harness.readers import mean_ms


def read(ctx):
    return mean_ms(ctx.get("plan_s", []))
