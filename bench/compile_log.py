"""Backend compiles and persistent-cache events, from JAX's own monitoring
hooks (a copy of ``chip_smoke.py:CompileLog``).  A persistent-cache hit
skips the backend compile; either one means a program was made ready."""

from __future__ import annotations

import jax


class CompileLog:
    """Counts since construction; one per process (listeners stay)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def programs_made(self) -> int:
        """Backend compiles plus programs loaded from the cache."""
        return self.compiles + self.hits

    def line(self) -> str:
        return (f"compile: {self.compiles} backend compiles in "
                f"{self.seconds:.3f}s, persistent cache {self.hits} hits, "
                f"{self.writes} misses")
