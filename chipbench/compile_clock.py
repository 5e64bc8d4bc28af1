"""Seconds and count of JAX compilations, from ``jax.monitoring``."""

from __future__ import annotations

import jax


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling programs (a load
    from the persistent cache counts as a compile), and how many backend
    compiles or cache loads it made."""

    EVENTS = "/jax/core/compile/"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith(self.EVENTS):
            self.seconds += duration
        if event == self.BACKEND:
            self.backend_compiles += 1
