"""Readers that more than one per-layer metric uses: a quantity that cells
of different end-to-end metrics report is one metric per end-to-end
metric (``compile_s.teps`` and ``compile_s.iter``), read the same way."""


def compile_s(ctx):
    """Seconds inside the measured window spent in JAX's compile path
    (tracing, lowering, backend compile or persistent-cache retrieval),
    the union of the spans that ``jax.monitoring`` reports.  The count of
    compiles and of cache hits goes to standard error."""
    return ctx["window"]["compile_s"]


def device_idle(ctx):
    """Share of the traced window in which no operation ran on the
    device: 100 * (1 - busy / window), busy being the union of the
    intervals of the device's ``XLA Ops`` events in the profiler trace."""
    trace = ctx["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
