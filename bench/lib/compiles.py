"""Counts the XLA compilations JAX reports, with their seconds.

JAX reports a backend compile through ``jax.monitoring`` whether the
program is compiled or loaded from the persistent cache, so an event inside
the measured window means that work was done there.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    def __init__(self):
        self.events: List[Tuple[float, str, float]] = []   # end, fun, secs
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.events.append((time.perf_counter(), kw.get("fun_name", "?"),
                                duration))

    def between(self, lo: float, hi: float) -> List[Tuple[str, float]]:
        """Compiles that ended inside [lo, hi] (perf_counter seconds)."""
        return [(f, d) for t, f, d in self.events if lo <= t <= hi]
