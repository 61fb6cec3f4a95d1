"""repro.obs — the dependency-free flight recorder (ISSUE 7).

``metrics``: process-wide counters/gauges/histograms with snapshot/
delta semantics and Prometheus text exposition.  ``trace``: span/event
tracing on an injectable clock, exported as Chrome trace-event JSON
(Perfetto-loadable), and ``span``, the program's span primitive, which
also lands in ``jax.profiler``'s trace.  ``record``: the FlightRecorder
tying both to per-round engine records; ``report``: the session-summary
renderer (``python -m repro.obs.report session.json``).

Nothing here imports jax/numpy at import time (``span`` imports
``jax.profiler`` on first use) — with recording and the profiler off,
instrumented hot paths pay one attribute read and an idle annotation.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               registry)
from repro.obs.record import (FlightRecorder, RoundRecord, get_recorder,
                              install, load_session, metrics_to_json,
                              recording)
from repro.obs.trace import ProgramSpan, Span, Tracer, span

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "FlightRecorder", "RoundRecord", "get_recorder", "install",
    "load_session", "metrics_to_json", "recording",
    "ProgramSpan", "Span", "Tracer", "span",
]
