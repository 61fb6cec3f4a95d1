"""The delta-PageRank cell at a size a test can hold: a whole run of it
is correct, and is not with the timed path broken underneath; the same
rounds in bfloat16 fail its limit; and its two readers read what they
should, and nothing without a trace.

The harness's look for a chip is skipped (``run_cell`` is driven
directly, on the CPU, with Pallas in interpret mode).
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import control_delta, graphs, reference_delta, run  # noqa: E402

CONFIG, TRAFFIC = "rmat20", "pagerank_delta"
PEAK = {"hbm_bytes_per_s": 819e9}


def load(kind, name):
    return run.load_json(os.path.join(ROOT, "bench", kind, f"{name}.json"))


def run_tiny(seed=2**31 + 99) -> dict:
    """The cell's graph, partition and engine at scale 8 on 4 shards."""
    import jax
    res = {"config": load("configs", CONFIG),
           "traffic": load("traffic", TRAFFIC),
           "end_to_end": [], "per_layer": []}
    res["config"]["graph"]["scale"] = 8
    res["config"]["partition"]["num_shards"] = 4
    return run.run_cell(res, seed, 0.0, False, jax.devices()[:1], PEAK,
                        time.perf_counter())


def stale_round(mp):
    """Every round leaves the ranks it was given as they were."""
    from repro import exchange
    real = exchange.delta_pagerank_round_stacked

    def round_fn(*a, **k):
        _, delta, chg, counts = real(*a, **k)
        return a[7], delta, chg, counts
    mp.setattr(exchange, "delta_pagerank_round_stacked", round_fn)


def half_dropped(mp):
    """Half of the edges' messages are left out."""
    from repro.core import engine
    real = engine.DeviceArrays.from_partition

    def from_partition(part):
        arrays = real(part)
        keep = np.arange(arrays.edge_mask.shape[1]) % 2 == 0
        return arrays._replace(edge_mask=arrays.edge_mask & keep)
    mp.setattr(engine.DeviceArrays, "from_partition",
               staticmethod(from_partition))


def collapse_skipped(mp):
    """The rhizome collapse between a vertex's replicas is left out."""
    from repro.exchange import rounds
    mp.setattr(rounds, "stacked_collapse",
               lambda sem, arrays, cfg, table: table)


def score_altered(mp):
    """One vertex's score is altered where it is produced."""
    from repro.core import engine
    real = engine.vertex_values

    def vertex_values(part, val):
        out = np.array(real(part, val))
        i = int(np.argmax(out))
        out[i] *= 1.001
        return out
    mp.setattr(engine, "vertex_values", vertex_values)


def tolerance_tripled(mp):
    """Rounds stop at three times the tolerance: the tail rounds are
    dropped early, an error the method's own truncation nearly hides."""
    from repro.core import engine
    real = engine.run_pagerank_delta

    def run_pagerank_delta(part, damping, tol, *a, **k):
        return real(part, damping, 3 * tol, *a, **k)
    mp.setattr(engine, "run_pagerank_delta", run_pagerank_delta)


FAULTS = [stale_round, half_dropped, collapse_skipped, score_altered,
          tolerance_tripled]


def test_sound_run_is_correct():
    out = run_tiny()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["checks"]) == {"max_rel_err", "rounds_rel_err",
                                  "unconverged_calls"}
    assert out["checks"]["unconverged_calls"]["value"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", FAULTS, ids=[f.__name__ for f in FAULTS])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = run_tiny()
    assert out["correct"] is False, out["checks"]
    assert out["failed"] >= 1


def test_tripled_tolerance_fails_only_against_the_rounds(monkeypatch):
    """Against the converged ranks the early stop hides in the method's
    truncation; against the float64 rounds at the same tolerance it
    does not."""
    tolerance_tripled(monkeypatch)
    checks = run_tiny()["checks"]
    assert checks["max_rel_err"]["value"] <= checks["max_rel_err"]["limit"]
    assert checks["rounds_rel_err"]["value"] > \
        checks["rounds_rel_err"]["limit"], checks


def test_driver_needs_the_resident_partition(monkeypatch):
    """A program without ``ResidentPartition`` fails at set-up."""
    from repro.core import engine
    monkeypatch.delattr(engine, "ResidentPartition")
    with pytest.raises(ImportError):
        run_tiny()


def driver_at(scale, seed):
    graph = dict(load("configs", CONFIG)["graph"], scale=scale)
    n, src, dst = graphs.generate(graph, seed)
    traffic = load("traffic", TRAFFIC)
    return run.load_module("drivers", traffic["app"]).Driver(
        traffic, n, src, dst, None, None, seed), n, src, dst


def correct(driver, answers):
    checks, failed, _ = driver.verify(answers)
    return failed == 0 and all(c["value"] <= c["limit"]
                               for c in checks.values()), checks


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_bfloat16_rounds_fail_the_limit(seed):
    driver, n, src, dst = driver_at(8, seed)
    got = control_delta.controls(driver, n, src, dst, 2)
    ok, checks = correct(driver, got["bfloat16"])
    assert not ok and checks["max_rel_err"]["value"] > \
        checks["max_rel_err"]["limit"], checks
    ok, checks = correct(driver, got["float32"])
    assert ok, checks


def test_verify_counts_the_reference_work():
    driver, n, src, dst = driver_at(8, 3)
    rank, rounds, frontier_edges = reference_delta.delta_rounds(
        n, src, dst, driver.damping, driver.tol, driver.max_rounds)
    checks, failed, work = driver.verify([(rank, rounds + 2)] * 3)
    assert failed == 0 and checks["unconverged_calls"]["value"] == 0
    assert 0 < frontier_edges <= rounds * src.size
    assert work["ref_rounds"] == 3 * rounds
    assert work["iterations"] == pytest.approx(
        3 * (rounds + frontier_edges / src.size) / 2)
    assert work["frontier_edges"] == 3 * frontier_edges
    assert work["rounds"] == 3 * (rounds + 2)
    checks, failed, _ = driver.verify([(rank, driver.max_rounds)])
    assert failed == 1 and checks["unconverged_calls"]["value"] == 1


def ctx(trace):
    return {"trace": trace, "peak": PEAK, "window": {"seconds": 40.0},
            "work": {"n": 1000, "edges": 5000, "calls": 2,
                     "iterations": 16, "ref_rounds": 20,
                     "frontier_edges": 60_000,
                     "rounds": 22}}


@pytest.mark.parametrize("name", ["hbm_roofline.delta",
                                  "round_host_s.delta"])
def test_readers_read_nothing_without_a_trace(name):
    assert run.load_module("metrics", name).read(ctx(None)) is None


def test_readers_on_a_synthetic_trace():
    c = ctx({"busy_s": 2.0, "window_s": 13.0})
    roof = run.load_module("metrics", "hbm_roofline.delta").read(c)
    least = 16 * 60_000 + 8 * 1000 * 20
    assert roof == pytest.approx(100.0 * least / 819e9 / 2.0)
    host = run.load_module("metrics", "round_host_s.delta").read(c)
    assert host == pytest.approx((13.0 - 2.0) / 22)
    per_round = run.load_module("metrics", "iteration_s").read(c)
    assert per_round == pytest.approx(40.0 / 16)
