"""Counts what JAX compiles or loads from its persistent cache, through
``jax.monitoring``.  Either one inside the measured window is a program
that was not warmed up."""

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """``programs`` counts every backend compile request (compiled or
    found in the persistent cache), ``cache_hits`` those found."""

    def __init__(self):
        import jax.monitoring as monitoring
        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.programs += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"programs": self.programs, "cache_hits": self.cache_hits,
                "seconds": self.seconds}
