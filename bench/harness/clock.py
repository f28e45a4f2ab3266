"""Compile time and compile count, from JAX's own monitoring events."""
from __future__ import annotations

import threading

__all__ = ["CompileClock"]


class CompileClock:
    """Seconds JAX spent lowering and compiling, and the number of
    backend compiles, since the clock was made.  Tracing nests inside
    lowering and is left out; a program served from the persistent cache
    is no backend compile."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    COUNTED = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            with self._lock:
                self.seconds += duration
                self.compiles += event == self.COUNTED

    def read(self):
        """(seconds, compiles) so far."""
        with self._lock:
            return self.seconds, self.compiles
