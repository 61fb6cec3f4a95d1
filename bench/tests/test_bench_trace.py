"""The yardstick's arithmetic on the CPU: trace reduction, compile spans,
least-byte counts, metric readers, and the refusals of the command."""
from __future__ import annotations

import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import compiles, devtrace, reference, run  # noqa: E402


def test_busy_and_gaps_of_known_intervals():
    ivs = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (9.0, 12.0)]
    assert devtrace.merge(ivs) == [(1.0, 3.0), (5.0, 6.0), (9.0, 12.0)]
    assert devtrace.busy(ivs, 0.0, 10.0) == pytest.approx(4.0)
    assert devtrace.gaps(ivs, 0.0, 10.0) == [(0.0, 1.0), (3.0, 5.0),
                                            (6.0, 9.0)]


def test_reduce_synthetic_trace():
    ops = {"/device:TPU:0": [("fusion", 1.0, 2.0), ("relax", 1.5, 3.0),
                             ("relax", 5.0, 6.0)]}
    host = [("bench.call", 0.0, 10.0), ("PjitFunction", 2.5, 10.0),
            ("compile", 3.0, 4.9), ("plan", 6.0, 10.0)]
    out = devtrace.reduce(ops, host, 0.0, 10.0)
    assert out["busy_s"] == pytest.approx(3.0)
    assert out["window_s"] == pytest.approx(10.0)
    assert out["breakdown"]["device_ops"] == [
        ["relax", pytest.approx(2.5)], ["fusion", pytest.approx(1.0)]]
    assert out["breakdown"]["idle_gaps"] == [
        ["plan", pytest.approx(4.0)], ["compile", pytest.approx(2.0)],
        ["no host event", pytest.approx(1.0)]]


def test_breakdown_counts_innermost_ops_by_short_name():
    ops = {"/device:TPU:0": [
        ("%while.3 = (s32[]) while(...)", 0.0, 5.0),
        ("%fusion.1 = f32[8] fusion(...)", 0.5, 2.0),
        ("%_fused.2 = f32[8] custom-call(...)", 2.0, 3.0),
        ("%fusion.1 = f32[8] fusion(...)", 6.0, 7.0)]}
    out = devtrace.reduce(ops, [], 0.0, 10.0)
    assert out["busy_s"] == pytest.approx(6.0)
    assert out["breakdown"]["device_ops"] == [
        ["%fusion.1", pytest.approx(2.5)], ["%_fused.2", pytest.approx(1.0)]]


def test_busy_averages_over_devices():
    ops = {"/device:TPU:0": [("a", 0.0, 4.0)],
           "/device:TPU:1": [("a", 0.0, 2.0)]}
    assert devtrace.reduce(ops, [], 0.0, 10.0)["busy_s"] == \
        pytest.approx(3.0)


def test_compile_seconds_count_nested_spans_once():
    watch = compiles.CompileWatch()
    watch.phase = "window"
    for start, end in [(0, 2), (1, 3), (5, 6)]:
        watch._span(compiles.SPAN_EVENTS[0], start, end)
    watch._span(compiles.BACKEND, 5.2, 5.8)
    watch._event(compiles.HITS)
    assert watch.summary("window") == {
        "compile_s": 4, "compiles": 1, "cache_hits": 1, "cache_misses": 0}
    assert watch.summary("setup")["compile_s"] == 0


def test_least_bytes_by_hand():
    # 4 vertices: 0->1, 0->2, 1->3, 2->3, 3->3 (self loop); search from 0
    n = 4
    src = np.array([0, 0, 1, 2, 3], np.int32)
    dst = np.array([1, 2, 3, 3, 3], np.int32)
    indptr = np.array([0, 2, 3, 4, 5])
    level = reference.bfs_levels(n, indptr, dst, 0)
    assert level.tolist() == [0, 1, 1, 2]
    out_deg = np.bincount(src, minlength=n)
    edges = reference.traversed_edges(out_deg, level)
    rounds = reference.bfs_rounds(level)
    assert (edges, rounds) == (5, 3)
    teps = run.load_module("metrics", "hbm_roofline.teps")
    assert teps.least_bytes(edges, n, rounds) == 16 * 5 + 8 * 4 * 3
    it = run.load_module("metrics", "hbm_roofline.iter")
    assert it.least_bytes(5, n, 10) == (16 * 5 + 8 * 4) * 10


def _ctx(trace):
    return {"setup": {"setup_s": 30.0, "partition_s": 20.0},
            "window": {"seconds": 10.0, "calls": 2, "compile_s": 4.0},
            "work": {"n": 4, "edges": 5, "traversed_edges": 10,
                     "rounds": 6, "iterations": 20},
            "trace": trace, "peak": {"hbm_bytes_per_s": 100.0}}


def read(name, ctx):
    return run.load_module("metrics", name).read(ctx)


def test_metric_readers():
    ctx = _ctx({"busy_s": 2.0, "window_s": 10.0})
    assert read("teps", ctx) == pytest.approx(1.0)
    assert read("iteration_s", ctx) == pytest.approx(0.5)
    assert read("setup_s", ctx) == 30.0
    assert read("partition_s", ctx) == 20.0
    assert read("compile_s.teps", ctx) == 4.0
    assert read("device_idle.iter", ctx) == pytest.approx(80.0)
    # (16*10 + 8*4*6) B at 100 B/s over 2 s busy
    assert read("hbm_roofline.teps", ctx) == pytest.approx(
        100 * (352 / 100) / 2)
    assert read("hbm_roofline.iter", ctx) == pytest.approx(
        100 * ((16 * 5 + 8 * 4) * 20 / 100) / 2)


@pytest.mark.parametrize("name", ["device_idle.teps", "hbm_roofline.iter"])
def test_device_readers_read_nothing_without_device_time(name):
    assert read(name, _ctx(None)) is None
    assert read(name, _ctx({"busy_s": 0.0, "window_s": 1.0})) is None


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_device_checks_refuse():
    peaks = run.load_json(os.path.join(ROOT, "bench", "peaks.json"))
    peaks = peaks["devices"]
    assert run.device_peak([_device("tpu", "TPU v5 lite")], 1,
                           peaks)["hbm_bytes_per_s"] == 819e9
    for devices, chips in (([_device("cpu", "cpu")], 1),
                           ([_device("tpu", "TPU v9 imaginary")], 1),
                           ([_device("tpu", "TPU v5 lite")], 4)):
        with pytest.raises(SystemExit) as e:
            run.device_peak(devices, chips, peaks)
        assert e.value.code != 0


def test_cli_refuses_cpu_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "rmat20.pagerank", "--seed", "3", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs a TPU" in p.stderr
