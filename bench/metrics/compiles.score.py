"""Executables JAX built inside the measured window (lowerings counted by
a `jax.monitoring` listener); 0 once set-up warmed every shape."""


def read(ctx):
    return ctx.get("compiles_in_window")
