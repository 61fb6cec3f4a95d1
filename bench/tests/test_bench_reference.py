"""The benchmark's own generators and references: deterministic per seed,
and in agreement with the program's plain references on tiny graphs."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import graphs, reference  # noqa: E402

GRAPHS = {
    "rmat": {"kind": "rmat", "scale": 9, "edge_factor": 8,
             "a": 0.45, "b": 0.25, "c": 0.15},
    "erdos_renyi": {"kind": "erdos_renyi", "scale": 9, "mean_degree": 6},
}
BIG_SEED = 2**31 + 1234567


@pytest.mark.parametrize("kind", sorted(GRAPHS))
def test_generators_deterministic_per_seed(kind):
    a = graphs.generate(GRAPHS[kind], BIG_SEED)
    b = graphs.generate(GRAPHS[kind], BIG_SEED)
    c = graphs.generate(GRAPHS[kind], BIG_SEED + 1)
    assert a[0] == b[0] == 512
    assert np.array_equal(a[1], b[1]) and np.array_equal(a[2], b[2])
    assert not (a[1].size == c[1].size and np.array_equal(a[2], c[2]))


@pytest.mark.parametrize("kind", sorted(GRAPHS))
def test_generated_edges_are_sorted_and_unique(kind):
    n, src, dst = graphs.generate(GRAPHS[kind], 5)
    key = src.astype(np.int64) * n + dst
    assert np.all(np.diff(key) > 0)
    assert src.dtype == dst.dtype == np.int32
    assert 0 <= src.min() and src.max() < n and dst.max() < n
    m = 8 * n if kind == "rmat" else 6 * n
    assert 0.5 * m < src.size <= m


def test_rmat_is_skewed_and_er_is_not():
    _, _, d_rmat = graphs.generate(GRAPHS["rmat"], 7)
    _, _, d_er = graphs.generate(GRAPHS["erdos_renyi"], 7)
    in_rmat = np.bincount(d_rmat, minlength=512)
    in_er = np.bincount(d_er, minlength=512)
    assert in_rmat.std() > 2 * in_er.std()


def _program_graph(n, src, dst):
    from repro.graph.graph import COOGraph
    return COOGraph(n, src, dst, None)


@pytest.mark.parametrize("kind", sorted(GRAPHS))
@pytest.mark.parametrize("seed", [1, 2])
def test_bfs_matches_program_reference(kind, seed):
    from repro.graph import reference as prog
    n, src, dst = graphs.generate(GRAPHS[kind], seed)
    indptr, indices = graphs.csr(n, src, dst)
    g = _program_graph(n, src, dst)
    for root in (0, int(np.argmax(np.diff(indptr))), n - 1):
        want = prog.bfs_levels(g, root)
        got = reference.bfs_levels(n, indptr, indices, root)
        assert np.array_equal(got, want)
        assert reference.UNREACHED == prog.UNREACHED


@pytest.mark.parametrize("kind", sorted(GRAPHS))
def test_pagerank_matches_program_reference(kind):
    from repro.graph import reference as prog
    n, src, dst = graphs.generate(GRAPHS[kind], 3)
    want = prog.pagerank(_program_graph(n, src, dst), 0.85, 10)
    got = reference.pagerank(n, src, dst, 0.85, 10)
    np.testing.assert_allclose(got, want, rtol=1e-12)


def test_comparisons():
    want = np.array([0, 1, 2, reference.UNREACHED])
    assert reference.level_mismatches(want.copy(), want) == 0
    assert reference.level_mismatches(np.array([0, 1, 3, 3]), want) == 2
    assert reference.level_mismatches(np.array([0, 1]), want) == 4
    w = np.array([1.0, 2.0])
    assert reference.max_rel_err(np.array([1.0, 2.002]), w) == \
        pytest.approx(1e-3)
    assert reference.max_rel_err(np.array([1.0, np.nan]), w) == np.inf
    assert reference.max_rel_err(np.array([1.0]), w) == np.inf


def test_giant_core_by_hand():
    # 0 -> 1 -> 2 -> 0 is a cycle; 2 has the most out-edges; 3 is only
    # reached, 4 only reaches, 5 is alone.
    src = np.array([0, 1, 2, 2, 4], np.int32)
    dst = np.array([1, 2, 0, 3, 0], np.int32)
    assert reference.giant_core(6, src, dst).tolist() == [0, 1, 2]


@pytest.mark.parametrize("kind", sorted(GRAPHS))
def test_search_keys_drawn_from_seed_in_giant_core(kind):
    from bench import run
    traffic = run.load_json(os.path.join(ROOT, "bench", "traffic",
                                         "bfs_roots.json"))
    Driver = run.load_module("drivers", "bfs").Driver
    n, src, dst = graphs.generate(GRAPHS[kind], BIG_SEED)
    a = Driver(traffic, n, src, dst, None, None, BIG_SEED)
    b = Driver(traffic, n, src, dst, None, None, BIG_SEED)
    c = Driver(traffic, n, src, dst, None, None, BIG_SEED + 1)
    assert np.array_equal(a.roots, b.roots) and a.warm_root == b.warm_root
    assert not np.array_equal(a.roots, c.roots)
    keys = np.append(a.roots, a.warm_root)
    assert len(a.roots) == traffic["roots"]
    assert np.unique(keys).size == keys.size
    assert np.isin(keys, reference.giant_core(n, src, dst)).all()
    assert (a.out_degree[keys] >= 1).all()
    indptr, indices = graphs.csr(n, src, dst)
    reach = {reference.traversed_edges(
        a.out_degree, reference.bfs_levels(n, indptr, indices, int(r)))
        for r in keys[:4]}
    assert len(reach) == 1
