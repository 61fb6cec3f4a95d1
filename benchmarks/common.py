"""Shared benchmark fixtures: laptop-scale analogs of the paper's datasets.

Scaled so every figure reproduces its paper counterpart's *shape* in
seconds, not hours: RMAT keeps (a=0.45,b=0.25,c=0.15); BA supplies the
WK/LJ-style heavy in-degree tail; ER is the low-skew control.

Importing this module points JAX's persistent compilation cache at its
home (``repro.compile_cache``).  It never chooses a platform: JAX uses
the accelerator when there is one, and CPU runs set
``JAX_PLATFORMS=cpu`` themselves.
"""
from __future__ import annotations

import os
import time

from repro.compile_cache import enable_compile_cache
from repro.graph import generators

enable_compile_cache()

DATASETS = {
    "E14": lambda: generators.erdos_renyi(1 << 14, avg_degree=9.0, seed=1),
    "R14": lambda: generators.rmat(14, edge_factor=16, seed=2),
    "BA14": lambda: generators.ba_skewed(1 << 14, m_per=8, seed=3),
    "AM-like": lambda: generators.rmat(14, edge_factor=5, a=0.30, b=0.25,
                                       c=0.25, seed=4),
}


def add_seed_arg(ap, default: int = 7):
    """Grow a bench arg parser a ``--seed`` flag: the base RNG seed for
    graph generation (and anything else stochastic), threaded through the
    engine/query benches so BENCH_*.json runs are reproducible
    run-to-run and recorded in the emitted report."""
    ap.add_argument("--seed", type=int, default=default,
                    help="base RNG seed for graph generation "
                         f"(default {default}; recorded in the report)")
    return ap


def add_grid_mode_arg(ap, default: str = "worklist"):
    """Grow a bench arg parser a ``--grid-mode`` flag: the fused kernel's
    launch shape for the worklist-capable bench variants (ISSUE 5) —
    'dense' (the classic early-exit grid), 'worklist' (host-planned
    live-cell launches), 'auto', or 'device_worklist' (on-device frontier
    compaction, ISSUE 8).  Recorded in the emitted BENCH json so the perf
    trajectory distinguishes dense from worklist runs.

    The default can be overridden without touching the command line via
    the ``REPRO_GRID_MODE`` env var — the CI device-worklist leg sets
    ``REPRO_GRID_MODE=device_worklist`` and reruns the tier-1 suite and
    bench smokes unchanged."""
    env = os.environ.get("REPRO_GRID_MODE")
    if env:
        default = env
    ap.add_argument("--grid-mode", default=default,
                    choices=("dense", "worklist", "auto",
                             "device_worklist"),
                    help="fused-kernel grid mode for worklist-capable "
                         f"variants (default {default}; env "
                         "REPRO_GRID_MODE overrides; recorded in the "
                         "report)")
    return ap


def disp_snap():
    """Snapshot the obs registry's engine dispatch / host-sync counters
    (summed over run labels) — the benches' ``dispatches_total`` and
    ``host_syncs_per_round`` columns are registry deltas across each
    variant's run, the same counters the shipped runners feed."""
    from repro import obs
    reg = obs.registry()
    d = sum(reg.counter("engine_dispatches_total").snapshot_values()
            .values())
    s = sum(reg.counter("engine_host_syncs_total").snapshot_values()
            .values())
    return d, s


def disp_delta(before):
    after = disp_snap()
    return after[0] - before[0], after[1] - before[1]


def reversed_graph(g):
    from repro.graph.graph import COOGraph
    return COOGraph(g.n, g.dst, g.src, g.weight)


def timed(fn, *args, repeats: int = 1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeats
    return out, dt * 1e6  # us


def emit(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")


def add_obs_out_arg(ap):
    """Grow a bench arg parser an ``--obs-out`` flag: also write the
    BENCH columns as a Prometheus text exposition rendered from the obs
    metrics registry (the same registry the engine/serving layers feed)."""
    ap.add_argument("--obs-out", default=None,
                    help="also write the report's numeric columns as a "
                         "Prometheus text exposition (obs registry)")
    return ap


def emit_report_metrics(report: dict, registry=None):
    """Re-emit every numeric column of a BENCH_*.json report through the
    obs metrics registry as ``bench_value{bench=...,key=...}`` gauges, so
    benchmark output and engine/serving telemetry share one exposition
    path.  Returns the registry used."""
    from repro import obs
    reg = registry if registry is not None else obs.registry()
    bench = str(report.get("bench", "bench"))
    g = reg.gauge("bench_value",
                  "numeric BENCH report columns (key = /-joined path)")

    def walk(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                if k in ("per_round", "notes"):  # summary columns only
                    continue
                walk(f"{prefix}/{k}" if prefix else str(k), v)
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(f"{prefix}/{i}", v)
        elif isinstance(node, (bool, int, float)):
            g.labels(bench=bench, key=prefix).set(float(node))

    walk("", report)
    return reg


def finish_report(report: dict, obs_out=None):
    """Common bench epilogue: re-emit the report through the obs registry
    and, with ``--obs-out``, write the Prometheus exposition next to the
    BENCH json."""
    reg = emit_report_metrics(report)
    if obs_out:
        with open(obs_out, "w") as fh:
            fh.write(reg.render_prometheus())
        print(f"wrote {obs_out}")
    return reg
