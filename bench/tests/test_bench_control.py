"""The controls of the comparison that decides ``correct``, at a size a
test can hold: each comes out as not correct through the cell's own
``verify``, and the bfloat16 BFS, whose levels are exact, does not."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import control, graphs, reference, run  # noqa: E402

def setup(config, traffic, seed):
    """The driver of ``traffic`` over the graph of ``config`` at scale 10."""
    graph = dict(run.load_json(os.path.join(
        ROOT, "bench", "configs", f"{config}.json"))["graph"], scale=10)
    traffic = run.load_json(os.path.join(
        ROOT, "bench", "traffic", f"{traffic}.json"))
    n, src, dst = graphs.generate(graph, seed)
    driver = run.load_module("drivers", traffic["app"]).Driver(
        traffic, n, src, dst, None, None, seed)
    return driver, n, src, dst


def correct(driver, answers):
    checks, failed, _ = driver.verify(answers)
    return failed == 0 and all(c["value"] <= c["limit"]
                               for c in checks.values()), checks


@pytest.mark.parametrize("config", ["rmat20", "er20"])
@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_pagerank_bfloat16_control_fails(config, seed):
    import jax.numpy as jnp
    driver, n, src, dst = setup(config, "pagerank_fixed", seed)
    got = control.controls("pagerank", driver, n, src, dst, 2)
    ok, checks = correct(driver, got["bfloat16"])
    assert not ok, checks
    f32 = control.pagerank_device(n, src, dst, 0.85, 10, jnp.float32)
    ok, checks = correct(driver, [f32])
    assert ok, checks


@pytest.mark.parametrize("config", ["rmat20", "er20"])
@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_bfs_controls(config, seed):
    driver, n, src, dst = setup(config, "bfs_roots", seed)
    got = control.controls("bfs", driver, n, src, dst, 2)
    ok, checks = correct(driver, got["one_level_short"])
    assert not ok and checks["level_mismatches"]["value"] >= 1, checks
    ok, checks = correct(driver, got["bfloat16"])
    assert ok and checks["level_mismatches"]["value"] == 0, checks


def test_bfs_device_matches_reference():
    import jax.numpy as jnp
    n, src, dst = graphs.generate(
        {"kind": "rmat", "scale": 8, "edge_factor": 8,
         "a": 0.45, "b": 0.25, "c": 0.15}, 4)
    indptr, indices = graphs.csr(n, src, dst)
    for root in (0, 17):
        want = reference.bfs_levels(n, indptr, indices, root)
        got = control.bfs_device(n, src, dst, root, jnp.float32)
        assert np.array_equal(got, want)
