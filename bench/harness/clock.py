"""Backend-compile counter (copied from ``chip_smoke.py``'s
``CompileClock``, so that a change to that script cannot move the
benchmark)."""
from __future__ import annotations

import jax


class CompileClock:
    """Counts XLA backend compiles and sums their seconds, through
    ``jax.monitoring``; a persistent-cache hit counts only its read."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1
