"""JAX's own compile clock (copied from ``chip_smoke.CompileMeter``)."""

from __future__ import annotations


class CompileMeter:
    """How many programs went to the backend, the seconds they spent there
    (compiling, or reading the persistent cache instead), and the persistent
    cache's hits and misses.  Tracing and lowering are python work that no
    cache saves; they are not counted.  ``jax.monitoring`` keeps a listener
    for the life of the process, so a process makes one meter."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
