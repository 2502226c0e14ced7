"""Seconds JAX spends tracing, lowering and compiling, and how many
backend compiles it ran, from JAX's own monitoring events."""

from __future__ import annotations

import jax

EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    def __init__(self):
        self.secs = dict.fromkeys(EVENTS, 0.0)
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.secs:
            self.secs[event] += duration
            self.compiles += event == EVENTS[2]

    def snapshot(self) -> dict:
        out = {e.rsplit("/", 1)[-1]: round(s, 3) for e, s in self.secs.items()}
        out["backend_compiles"] = self.compiles
        return out
