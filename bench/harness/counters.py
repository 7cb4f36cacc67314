"""Counts of executables JAX builds, from `jax.monitoring` events: every
lowering of a jitted function to an executable (an in-memory cache miss),
and of those the ones the backend compiled rather than loaded from the
persistent cache."""

from __future__ import annotations

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Registers listeners once; read `lowered`, `compiled`, `cache_hits`."""

    def __init__(self):
        import jax

        self.lowered = 0
        self.compiled = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == LOWER_EVENT:
            self.lowered += 1
        elif event == COMPILE_EVENT:
            self.compiled += 1

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"lowered": self.lowered, "compiled": self.compiled,
                "cache_hits": self.cache_hits}
