"""The reduction of the program's spans and scopes (``bench/spans.py``)
and its seven readers, on synthetic traces and on a CPU profile."""
from __future__ import annotations

import glob
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import devtrace, run, spans  # noqa: E402

HOST = [("bench.window", 0.0, 10.0), ("bench.call", 0.0, 10.0),
        ("app.pagerank", 0.5, 9.5), ("engine.upload", 0.5, 1.0),
        ("engine.plan", 1.0, 1.25), ("engine.dispatch", 1.25, 4.0),
        ("PjitFunction(scan)", 1.5, 4.0), ("engine.sync", 4.0, 9.0),
        ("engine.upload", 9.25, 9.5), ("engine.upload", 9.3, 9.4),
        ("engine.sync", 9.8, 10.5)]
OPS = {"/device:TPU:0": [
    ("%while.1 = while(...)", 4.0, 8.0, "jit(scan)/while"),
    ("%fusion.41 = fusion(...)", 4.0, 6.0,
     "jit(scan)/while/body/exchange/relax/gather"),
    ("%fused_relax_reduce = custom-call(...)", 6.0, 6.5,
     "jit(scan)/while/body/exchange/relax/fused_relax_reduce"),
    ("%fusion.44 = fusion(...)", 6.5, 7.5,
     "jit(scan)/while/body/collapse/reduce_min"),
    ("%fusion.3 = fusion(...)", 7.5, 7.75,
     "jit(scan)/while/body/exchange/scatter-add"),
    ("%fusion.2 = fusion(...)", 7.75, 8.0, "jit(scan)/while/body/mul"),
    ("%copy.1 = copy(...)", 11.0, 12.0, None)]}


def test_scope_is_innermost_known_name():
    assert spans.scope_of("jit(f)/exchange/relax/gather") == "relax"
    assert spans.scope_of("jit(f)/relax/x/exchange/y") == "exchange"
    assert spans.scope_of("jit(f)/collapse/add") == "collapse"
    assert spans.scope_of("jit(f)/relaxed/add") == spans.UNSCOPED
    assert spans.scope_of(None) == spans.UNSCOPED


def test_span_seconds_are_unions_inside_the_window():
    got = spans.span_seconds(HOST, 0.0, 10.0)
    assert set(got) == {"app.pagerank", "engine.upload", "engine.plan",
                        "engine.dispatch", "engine.sync"}
    assert got["app.pagerank"] == pytest.approx(9.0)
    assert got["engine.upload"] == pytest.approx(0.75)   # nested once
    assert got["engine.plan"] == pytest.approx(0.25)
    assert got["engine.dispatch"] == pytest.approx(2.75)
    assert got["engine.sync"] == pytest.approx(5.2)      # clipped at 10


def test_scope_seconds_of_innermost_ops_averaged_over_devices():
    got = spans.scope_seconds(OPS, 0.0, 10.0)
    assert got == {"relax": pytest.approx(2.5),
                   "collapse": pytest.approx(1.0),
                   "exchange": pytest.approx(0.25),
                   "unscoped": pytest.approx(0.25)}
    two = dict(OPS, **{"/device:TPU:1": OPS["/device:TPU:0"][1:2]})
    assert spans.scope_seconds(two, 0.0, 10.0)["relax"] == \
        pytest.approx((2.5 + 2.0) / 2)


def _ctx(trace):
    return {"setup": {"setup_s": 30.0, "partition_s": 20.0},
            "window": {"seconds": 10.0, "calls": 2, "compile_s": 4.0},
            "work": {"n": 4, "edges": 5, "iterations": 20},
            "trace": trace, "peak": {"hbm_bytes_per_s": 100.0}}


def _traced():
    ops3 = {d: [op[:3] for op in ops] for d, ops in OPS.items()}
    return devtrace.reduce(ops3, HOST, 0.0, 10.0)


NEW = {"upload_s.iter": 0.75, "plan_s.iter": 0.25,
       "dispatch_s.iter": 2.75, "sync_s.iter": 5.2,
       "relax_s.iter": 2.5, "exchange_s.iter": 0.25,
       "collapse_s.iter": 1.0}


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_readers_by_hand(name):
    trace = dict(_traced(), **spans.reduce(OPS, HOST, 0.0, 10.0))
    got = run.load_module("metrics", name).read(_ctx(trace))
    assert got == pytest.approx(NEW[name] / 20)


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_readers_read_nothing_without_spans(name):
    read = run.load_module("metrics", name).read
    assert read(_ctx(None)) is None
    assert read(_ctx(_traced())) is None          # no span or scope keys
    assert read(_ctx(dict(_traced(), spans={}, scopes={}))) is None
    assert read(_ctx(dict(_traced(), spans={},
                          scopes={"unscoped": 4.0}))) is None


def test_a_scope_the_window_never_ran_reads_zero():
    trace = dict(_traced(), spans={"engine.sync": 1.0},
                 scopes={"relax": 2.0, "unscoped": 1.0})
    for name in ("exchange_s.iter", "collapse_s.iter", "plan_s.iter"):
        assert run.load_module("metrics", name).read(_ctx(trace)) == 0.0


@pytest.mark.parametrize("name", ["partition_s", "compile_s.iter",
                                  "device_idle.iter", "hbm_roofline.iter"])
def test_existing_readers_unchanged_by_span_keys(name):
    """The four accepted readers read the same value from a trace
    reduction with and without the span and scope keys, and
    ``devtrace.reduce`` gives what it gave before on the same trace."""
    traced = _traced()
    assert traced["busy_s"] == pytest.approx(4.0)
    assert traced["breakdown"]["device_ops"][0] == [
        "%fusion.41", pytest.approx(2.0)]
    read = run.load_module("metrics", name).read
    with_spans = dict(traced, **spans.reduce(OPS, HOST, 0.0, 10.0))
    assert read(_ctx(with_spans)) == read(_ctx(traced))


def test_read_of_a_cpu_profile(tmp_path):
    """On a real profile (CPU): the program's spans inside the window,
    and the trace's own HLO names the exchange layer's scopes; device
    scopes need a device plane, which a CPU profile lacks."""
    import jax
    import numpy as np
    from repro import apps
    from repro.core.engine import EngineConfig
    from repro.graph import generators
    g = generators.rmat(6, edge_factor=4, seed=1)
    cfg = EngineConfig(use_pallas=True, grid_mode="worklist")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            scores, _ = apps.pagerank(g, iters=2, num_shards=2, cfg=cfg)
    finally:
        jax.profiler.stop_trace()
    assert np.isfinite(scores).all()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    got = spans.read(path)
    assert {"app.pagerank", "engine.upload", "engine.dispatch",
            "engine.sync"} <= set(got["spans"])
    assert got["spans"]["engine.dispatch"] < got["spans"]["app.pagerank"]
    assert got["scopes"] == {}
    with open(path, "rb") as f:
        names = spans.trace_op_names(f.read())
    scan, = [p for p in names if p.startswith("jit_scan(")]
    parts = {part for op in names[scan].values() for part in op.split("/")}
    assert parts >= set(spans.SCOPES)
