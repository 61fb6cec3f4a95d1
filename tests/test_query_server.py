"""QueryServer continuous batching (ISSUE 2 tentpole).

Covers: mixed-kind request correctness vs numpy references, eviction the
round a lane converges (per-request rounds == the solo run's), mid-flight
admission into a lane freed while other lanes are still live (tested, not
demoed — the acceptance criterion), and no head-of-line blocking (a
short query completes before a long one admitted earlier).
"""
import numpy as np
import pytest

from repro.core import engine
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators, reference
from repro.graph.graph import COOGraph
from repro.query import QueryServer

UNREACHED = np.iinfo(np.int32).max


def _path_graph(n):
    src = np.arange(n - 1, dtype=np.int32)
    return COOGraph(n, src, (src + 1).astype(np.int32), None)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_server_mixed_kinds_match_references(use_pallas):
    g = generators.rmat(7, edge_factor=5, seed=5).with_random_weights(seed=5)
    deg = np.argsort(-g.out_degrees())
    part = build_partition(g, PartitionConfig(num_shards=4, rpvo_max=2))
    srv = QueryServer(part, n_lanes=3, ppr_lanes=2,
                      cfg=engine.EngineConfig(use_pallas=use_pallas))
    r0, r1, r2 = int(deg[0]), int(deg[1]), int(deg[4])
    q_bfs = srv.submit("bfs", r0)
    q_sssp = srv.submit("sssp", r1)
    q_reach = srv.submit("reachability", r2)
    q_msbfs = srv.submit("bfs", [r1, r2])          # multi-source
    results = srv.run()
    assert set(results) == {q_bfs, q_sssp, q_reach, q_msbfs}

    np.testing.assert_array_equal(results[q_bfs].values,
                                  reference.bfs_levels(g, r0))
    ref_d = reference.sssp_dijkstra(g, r1)
    finite = np.isfinite(ref_d)
    np.testing.assert_allclose(results[q_sssp].values[finite],
                               ref_d[finite], rtol=1e-5)
    assert not np.isfinite(results[q_sssp].values[~finite]).any()
    np.testing.assert_array_equal(
        results[q_reach].values,
        reference.bfs_levels(g, r2) != UNREACHED)
    ms_want = np.minimum(reference.bfs_levels(g, r1),
                         reference.bfs_levels(g, r2))
    np.testing.assert_array_equal(results[q_msbfs].values, ms_want)


def test_server_ppr_requests_match_reference():
    g = generators.rmat(7, edge_factor=5, seed=8)
    from repro.apps.pagerank import _pr_graph
    part = build_partition(_pr_graph(g),
                           PartitionConfig(num_shards=4, rpvo_max=2))
    deg = np.argsort(-g.out_degrees())
    srv = QueryServer(part, n_lanes=1, ppr_lanes=2)
    qa = srv.submit("ppr", int(deg[0]), damping=0.85, tol=1e-9)
    qb = srv.submit("ppr", int(deg[3]), damping=0.6, tol=1e-9)
    results = srv.run()
    for qid, seed, d in ((qa, int(deg[0]), 0.85), (qb, int(deg[3]), 0.6)):
        want = reference.personalized_pagerank(g, seed, d, tol=1e-12)
        np.testing.assert_allclose(results[qid].values, want,
                                   rtol=1e-4, atol=1e-7)


def test_server_evicts_on_convergence_with_solo_round_counts():
    """A lane is freed the round its query converges; the per-request
    round count equals the solo engine run's iteration count."""
    g = generators.rmat(7, edge_factor=4, seed=2).with_random_weights(seed=2)
    deg = np.argsort(-g.out_degrees())
    part = build_partition(g, PartitionConfig(num_shards=4, rpvo_max=2))
    srv = QueryServer(part, n_lanes=2)
    roots = [int(deg[0]), int(deg[2])]
    qids = [srv.submit("bfs", r) for r in roots]
    results = srv.run()
    from repro.apps import bfs as solo_bfs
    for qid, root in zip(qids, roots):
        _, solo_stats, _ = solo_bfs(g, root, part=part)
        assert results[qid].rounds == int(solo_stats.iterations)
        assert results[qid].messages == int(solo_stats.messages)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_server_admits_into_lane_freed_mid_flight(use_pallas):
    """The acceptance criterion: with both lanes busy, a queued request
    must be admitted into the lane a short query frees while the long
    query is STILL running — and the short queries must not wait behind
    the long one (no head-of-line blocking)."""
    n = 40
    g = _path_graph(n)
    part = build_partition(g, PartitionConfig(num_shards=4, rpvo_max=1))
    srv = QueryServer(part, n_lanes=2,
                      cfg=engine.EngineConfig(use_pallas=use_pallas))
    q_long = srv.submit("bfs", 0)          # n-1 rounds down the path
    q_short1 = srv.submit("bfs", n - 3)    # 2 rounds
    q_short2 = srv.submit("bfs", n - 5)    # queued: both lanes busy
    results = srv.run()
    assert set(results) == {q_long, q_short1, q_short2}

    long_r, s1, s2 = results[q_long], results[q_short1], results[q_short2]
    # short2 was admitted into short1's freed lane while long was live...
    assert s2.admitted_tick > 0                      # had to wait for a lane
    assert s2.admitted_tick > s1.completed_tick      # freed by short1
    assert s2.admitted_tick < long_r.completed_tick  # mid-flight, long live
    assert s2.lane == s1.lane and s2.lane != long_r.lane
    # ...and neither short query waited for the long one to finish
    assert s1.completed_tick < long_r.completed_tick
    assert s2.completed_tick < long_r.completed_tick

    np.testing.assert_array_equal(long_r.values, reference.bfs_levels(g, 0))
    np.testing.assert_array_equal(s1.values,
                                  reference.bfs_levels(g, n - 3))
    np.testing.assert_array_equal(s2.values,
                                  reference.bfs_levels(g, n - 5))
    # n-1 relax rounds down the path + the final no-improvement round that
    # detects convergence (same count as the solo engine's `iterations`)
    assert long_r.rounds == n


def test_server_occupancy_and_queue_drain():
    """More requests than lanes: everything completes, occupancy is
    tracked, and lanes never exceed capacity."""
    g = generators.rmat(7, edge_factor=4, seed=4)
    deg = np.argsort(-g.out_degrees())
    part = build_partition(g, PartitionConfig(num_shards=4, rpvo_max=2))
    srv = QueryServer(part, n_lanes=2)
    qids = [srv.submit("bfs", int(deg[i])) for i in range(6)]
    results = srv.run()
    assert set(results) == set(qids)
    assert srv.queue == []
    assert 0.0 < srv.occupancy() <= 1.0
    assert max(srv.occupancy_trace) <= 2     # min pool capacity respected
    for qid in qids:
        assert results[qid].latency_s >= 0.0


def test_server_rejects_unknown_kind():
    g = generators.ring(16)
    part = build_partition(g, PartitionConfig(num_shards=2))
    srv = QueryServer(part, n_lanes=1)
    with pytest.raises(ValueError, match="unknown query kind"):
        srv.submit("pagerank-global", 0)


def test_server_rejects_multi_seed_ppr():
    """ppr personalization is single-seed; a seed list must fail loudly at
    submit instead of silently truncating to the first vertex."""
    g = generators.ring(16)
    part = build_partition(g, PartitionConfig(num_shards=2))
    srv = QueryServer(part, n_lanes=1)
    with pytest.raises(ValueError, match="single personalization seed"):
        srv.submit("ppr", [0, 1])


def test_server_rejects_submit_into_empty_pool():
    """A request whose pool has zero lanes could never be admitted; it
    must fail at submit, not sit in the queue while run() spins."""
    g = generators.ring(16)
    part = build_partition(g, PartitionConfig(num_shards=2))
    srv = QueryServer(part, n_lanes=1, ppr_lanes=0)
    with pytest.raises(ValueError, match="no lanes"):
        srv.submit("ppr", 0)


def test_server_worklist_mode_plans_each_tick():
    """Under grid_mode='worklist' the stacked pools plan a host worklist
    from the OR-across-lanes frontier every tick (single-round ticks),
    with answers identical to the dense-grid server's."""
    g = generators.rmat(7, edge_factor=5, seed=5).with_random_weights(seed=5)
    from repro.apps.pagerank import _pr_graph
    part = build_partition(_pr_graph(g),
                           PartitionConfig(num_shards=4, rpvo_max=2))
    deg = np.argsort(-g.out_degrees())
    burst = [("bfs", int(deg[0])), ("sssp", int(deg[1])),
             ("ppr", int(deg[2]))]
    answers = {}
    for grid_mode in ("dense", "worklist"):
        srv = QueryServer(part, n_lanes=2, ppr_lanes=1, tick_rounds=4,
                          cfg=engine.EngineConfig(use_pallas=True,
                                                  grid_mode=grid_mode))
        planned = grid_mode == "worklist"
        assert (srv.min_pool._planner is not None) == planned
        assert (srv.ppr_pool._planner is not None) == planned
        qids = [srv.submit(kind, src, tol=1e-9) for kind, src in burst]
        res = srv.run()
        answers[grid_mode] = [res[q].values for q in qids]
    for kind, got, want in zip([k for k, _ in burst], answers["worklist"],
                               answers["dense"]):
        if kind == "ppr":
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-9)
        else:
            np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(answers["worklist"][0],
                                  reference.bfs_levels(g, int(deg[0])))
