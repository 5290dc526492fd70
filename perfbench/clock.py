"""Count what jax compiles, from its own monitoring events.

A copy of the listener in ``chip_smoke.compile_clock``: the union of the
trace, lowering and backend-compile spans in seconds, the backend
compiles, and the programs served from the persistent compilation cache.
"""

from __future__ import annotations

import jax

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        self.spans: list[tuple[float, float]] = []
        self.compiles = 0
        self.cache_hits = 0

    def _on_span(self, event, start, end, **_):
        if event in _COMPILE_EVENTS:
            self.spans.append((start, end))
            self.compiles += event == _COMPILE_EVENTS[-1]

    def _on_event(self, event, **_):
        self.cache_hits += event == _CACHE_HIT

    def __enter__(self):
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_time_span_listener(self._on_span)
        jax.monitoring.unregister_event_listener(self._on_event)

    @property
    def seconds(self) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self.spans):
            total += max(0.0, end - max(start, reach))
            reach = max(reach, end)
        return total
