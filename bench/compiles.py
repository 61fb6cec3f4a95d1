"""Counts JAX's compilations and persistent-cache lookups per phase of a
run, from ``jax.monitoring`` events.

``compile_s`` of a phase is the length of the union of the time spans of
JAX's compile path (tracing to a jaxpr, lowering to MLIR, and the backend
compile or persistent-cache retrieval), so nested spans count once.
"""
from __future__ import annotations

import collections

from bench import devtrace

SPAN_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")
BACKEND = "/jax/core/compile/backend_compile_duration"
HITS = "/jax/compilation_cache/cache_hits"
MISSES = "/jax/compilation_cache/cache_misses"


class CompileWatch:
    """Install once per process (``jax.monitoring`` cannot remove a
    listener); ``phase`` names where events land."""

    def __init__(self):
        import jax.monitoring as mon
        self.reset()
        mon.register_event_time_span_listener(self._span)
        mon.register_event_listener(self._event)

    def reset(self):
        self.phase = "setup"
        self.spans = collections.defaultdict(list)
        self.counts = collections.defaultdict(collections.Counter)

    def _span(self, event, start, end, **_):
        if event in SPAN_EVENTS:
            self.spans[self.phase].append((start, end))
            if event == BACKEND:
                self.counts[self.phase]["compiles"] += 1

    def _event(self, event, **_):
        if event == HITS:
            self.counts[self.phase]["cache_hits"] += 1
        elif event == MISSES:
            self.counts[self.phase]["cache_misses"] += 1

    def summary(self, phase: str) -> dict:
        c = self.counts[phase]
        spans = devtrace.merge(self.spans[phase])
        return {"compile_s": sum(e - s for s, e in spans),
                "compiles": c["compiles"], "cache_hits": c["cache_hits"],
                "cache_misses": c["cache_misses"]}
