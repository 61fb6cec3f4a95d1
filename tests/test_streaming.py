"""Streaming graphs: the mutation-differential suite (ISSUE 9).

Pins the streaming path's exactness: after EVERY interleaved
insert/delete batch, incrementally-maintained results equal a cold
fixpoint on the final graph — BFS/SSSP/CC bit-identical (min semirings
are order-independent over the same f32 path sums), delta-PageRank
within its residual tolerance — and the spliced partition equals a
from-scratch ``build_partition`` field for field, across the
jnp/fused × dense/worklist/device_worklist × stacked/lanes matrix
(sharded runs in a subprocess with forced host devices).  The
adaptive-rhizome split test additionally holds the planner mirror and
the kernel's ``with_debug`` counters fixed across splice vs rebuild.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import actions, engine
from repro.core.partition import PartitionConfig, build_partition
from repro.core.streaming import (StreamingGraph, invalidate_unsupported,
                                  _pr_weights)
from repro.graph import generators, reference

UNREACHED = np.iinfo(np.int32).max


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _to_levels(lv):
    out = np.full(lv.size, UNREACHED, np.int64)
    fin = np.isfinite(lv)
    out[fin] = lv[fin].astype(np.int64)
    return out


def _canon(lbl):
    m = {}
    out = np.empty(len(lbl), np.int64)
    for i, x in enumerate(lbl):
        out[i] = m.setdefault(x, len(m))
    return out


def _assert_parts_equal(got, want):
    for f in dataclasses.fields(want):
        if f.name in ("cfg", "metrics"):
            continue
        a, b = getattr(got, f.name), getattr(want, f.name)
        if isinstance(a, np.ndarray):
            assert np.array_equal(a, b), f.name
        else:
            assert a == b, f.name


def _random_batch(rng, n, k_ins, k_del, g):
    s = rng.integers(0, n, k_ins).astype(np.int32)
    d = rng.integers(0, n, k_ins).astype(np.int32)
    w = rng.integers(1, 10, k_ins).astype(np.float32)
    if k_del and g.num_edges > k_del:
        idx = rng.choice(g.num_edges, k_del, replace=False)
        return (s, d, w), (g.src[idx], g.dst[idx])
    return (s, d, w), None


def _check_all(sg, root, pr_tol):
    """Every tracked result vs a cold oracle on the CURRENT graph, and
    min apps bit-identical vs a cold engine run on the SAME partition."""
    gf = sg.g
    np.testing.assert_array_equal(
        _to_levels(sg.values("bfs", root)), reference.bfs_levels(gf, root))
    want = reference.sssp_dijkstra(gf, root)
    got = sg.values("sssp", root)
    fin = np.isfinite(want)
    assert (np.isfinite(got) == fin).all()
    np.testing.assert_array_equal(got[fin].astype(np.float32),
                                  want[fin].astype(np.float32))
    np.testing.assert_array_equal(
        _canon(sg.values("cc").tolist()),
        _canon(reference.connected_components(gf).tolist()))
    # bit-identity: cold fixpoint on the spliced partition
    part = sg.view("base").part
    init = engine.init_values(part, actions.SSSP, {root: 0.0})
    val, _ = engine.run_stacked(actions.SSSP, part, init,
                                engine.EngineConfig())
    np.testing.assert_array_equal(engine.vertex_values(part, val),
                                  sg.values("sssp", root))
    if ("pagerank", None) in sg.tracked:
        part_pr = build_partition(_pr_weights(gf), sg.pcfg)
        rank_t, _ = engine.run_pagerank_delta(
            part_pr, damping=0.85, tol=pr_tol, cfg=engine.EngineConfig())
        want_pr = engine.vertex_values(part_pr, rank_t)
        err = float(np.abs(sg.values("pagerank") - want_pr).max())
        # each vertex may keep a sub-tol residual per in-edge per run
        assert err < 200 * pr_tol, err


def _drive(sg, rng, root, batches=4, k_ins=8, k_del=4, pr_tol=1e-7):
    for b in range(batches):
        (s, d, w), dele = _random_batch(
            rng, sg.g.n, k_ins, k_del if b % 2 else 0, sg.g)
        sg.insert_edges(s, d, w)
        if dele is not None:
            sg.delete_edges(*dele)
        info = sg.commit()
        _check_all(sg, root, pr_tol)
        _assert_parts_equal(sg.view("base").part,
                            build_partition(sg.g, sg.pcfg))
        for key, ms in info.maint.items():
            assert ms.mode == "warm"
    return info


# --------------------------------------------------------------------------
# the differential matrix (satellite 1)
# --------------------------------------------------------------------------

MATRIX = [
    # (use_pallas, grid_mode, runner)  — every axis value covered
    (False, "dense", "stacked"),
    (True, "dense", "stacked"),
    (True, "worklist", "stacked"),
    (False, "dense", "lanes"),          # Q=3 laned maintenance
    (True, "device_worklist", "lanes"),
]


@pytest.mark.parametrize("use_pallas,grid_mode,runner", MATRIX)
def test_mutation_differential(use_pallas, grid_mode, runner):
    cfg = (engine.EngineConfig(use_pallas=True, grid_mode=grid_mode)
           if use_pallas else engine.EngineConfig())
    g = generators.rmat(6, edge_factor=6, seed=3).with_random_weights(seed=3)
    pcfg = PartitionConfig(num_shards=4, rpvo_max=3,
                           local_edge_list_size=8, seed=9)
    sg = StreamingGraph(g, pcfg, cfg=cfg, runner=runner)
    root = int(g.src[0])
    sg.track("bfs", root)
    sg.track("sssp", root)
    if runner == "lanes":
        sg.track("sssp", int(g.dst[0]))   # third lane in the group run
    sg.track("cc")
    sg.track("pagerank", tol=1e-7)
    _drive(sg, np.random.default_rng(0), root)


def test_mutation_differential_q1_single_lane():
    """Q=1: a single tracked min query still goes through the laned
    group path."""
    g = generators.rmat(6, edge_factor=5, seed=4)
    pcfg = PartitionConfig(num_shards=4, rpvo_max=2,
                           local_edge_list_size=8, seed=2)
    sg = StreamingGraph(g, pcfg, runner="lanes")
    root = int(g.src[0])
    sg.track("bfs", root)
    rng = np.random.default_rng(7)
    for _ in range(3):
        (s, d, w), dele = _random_batch(rng, g.n, 6, 3, sg.g)
        sg.insert_edges(s, d, w)
        if dele is not None:
            sg.delete_edges(*dele)
        sg.commit()
        np.testing.assert_array_equal(
            _to_levels(sg.values("bfs", root)),
            reference.bfs_levels(sg.g, root))


def test_mutation_differential_sharded():
    """The sharded runner (lanes × shard_map with real collectives),
    under forced host devices in a subprocess."""
    prog = textwrap.dedent("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core.partition import PartitionConfig
        from repro.core.streaming import StreamingGraph
        from repro.graph import generators, reference

        g = generators.rmat(6, edge_factor=6, seed=3)\\
            .with_random_weights(seed=3)
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8,), ("data",))
        pcfg = PartitionConfig(num_shards=8, rpvo_max=3,
                               local_edge_list_size=8, seed=9)
        sg = StreamingGraph(g, pcfg, runner="sharded", mesh=mesh,
                            axis_names=("data",))
        root = int(g.src[0])
        sg.track("bfs", root); sg.track("sssp", root)
        sg.track("pagerank", tol=1e-7)
        rng = np.random.default_rng(1)
        for batch in range(3):
            s = rng.integers(0, g.n, 6).astype(np.int32)
            d = rng.integers(0, g.n, 6).astype(np.int32)
            sg.insert_edges(s, d, rng.integers(1, 10, 6).astype(np.float32))
            if batch == 1:
                idx = rng.choice(sg.g.num_edges, 4, replace=False)
                sg.delete_edges(sg.g.src[idx], sg.g.dst[idx])
            sg.commit()
            want = reference.sssp_dijkstra(sg.g, root)
            got = sg.values("sssp", root)
            fin = np.isfinite(want)
            assert (np.isfinite(got) == fin).all()
            np.testing.assert_array_equal(
                got[fin].astype(np.float32), want[fin].astype(np.float32))
            lv = sg.values("bfs", root)
            out = np.full(g.n, np.iinfo(np.int32).max, np.int64)
            f2 = np.isfinite(lv); out[f2] = lv[f2].astype(np.int64)
            np.testing.assert_array_equal(
                out, reference.bfs_levels(sg.g, root))
        print("OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + ([os.environ["PYTHONPATH"]]
                      if os.environ.get("PYTHONPATH") else [])))
    res = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "OK" in res.stdout


# --------------------------------------------------------------------------
# property-based schedules (hypothesis, when available)
# --------------------------------------------------------------------------

def test_hypothesis_random_schedules():
    pytest.importorskip("hypothesis", reason="hypothesis not installed")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           scale=st.integers(5, 6),
           batches=st.integers(1, 3))
    def run(seed, scale, batches):
        rng = np.random.default_rng(seed)
        g = generators.rmat(scale, edge_factor=5,
                            seed=seed % 1000).with_random_weights(
                                seed=seed % 997)
        pcfg = PartitionConfig(num_shards=4, rpvo_max=3,
                               local_edge_list_size=8,
                               seed=int(rng.integers(0, 100)))
        sg = StreamingGraph(g, pcfg)
        root = int(g.src[0])
        sg.track("bfs", root)
        sg.track("sssp", root)
        sg.track("cc")
        for _ in range(batches):
            (s, d, w), dele = _random_batch(
                rng, g.n, int(rng.integers(1, 10)),
                int(rng.integers(0, 6)), sg.g)
            sg.insert_edges(s, d, w)
            if dele is not None:
                sg.delete_edges(*dele)
            sg.commit()
            _check_all(sg, root, 1e-7)
            _assert_parts_equal(sg.view("base").part,
                                build_partition(sg.g, sg.pcfg))

    run()


# --------------------------------------------------------------------------
# delete-side support invalidation is sound AND tight
# --------------------------------------------------------------------------

def test_invalidate_unsupported_exact_region():
    # path 0->1->2->3->4 plus a backup edge 0->3 (weight = old dist 3)
    from repro.graph.graph import COOGraph
    n = 5
    g0 = COOGraph(n, np.array([0, 1, 2, 3], np.int32),
                  np.array([1, 2, 3, 4], np.int32),
                  np.ones(4, np.float32))
    vals = np.array([0, 1, 2, 3, 4], np.float32)
    pinned = np.zeros(n, bool)
    pinned[0] = True
    # delete 2->3: 3 and (transitively) 4 lose support
    g1 = COOGraph(n, np.array([0, 1, 3], np.int32),
                  np.array([1, 2, 4], np.int32), np.ones(3, np.float32))
    inv = invalidate_unsupported(g1, vals, [2], [3], [1.0], pinned,
                                 unit_w=False)
    np.testing.assert_array_equal(inv, [0, 0, 0, 1, 1])
    # same deletion but an equal-cost alternate path keeps 3 (and so 4)
    g2 = COOGraph(n, np.array([0, 1, 0, 3], np.int32),
                  np.array([1, 2, 3, 4], np.int32),
                  np.array([1, 1, 3, 1], np.float32))
    inv = invalidate_unsupported(g2, vals, [2], [3], [1.0], pinned,
                                 unit_w=False)
    np.testing.assert_array_equal(inv, [0, 0, 0, 0, 0])


def test_deletes_only_relift_affected_region():
    """A delete far from most of the graph re-lifts only its cone:
    warm messages ≪ cold messages."""
    g = generators.rmat(8, edge_factor=8, seed=11)
    pcfg = PartitionConfig(num_shards=8, rpvo_max=4,
                           local_edge_list_size=8, seed=1)
    sg = StreamingGraph(g, pcfg)
    root = int(np.argmax(g.out_degrees()))
    sg.track("bfs", root)
    # cold baseline on the same engine config
    part = sg.view("base").part
    init = engine.init_values(part, actions.BFS, {root: 0.0})
    _, cold = engine.run_stacked(actions.BFS, part, init,
                                 engine.EngineConfig())
    # delete one reachable leaf-ish edge
    lv = sg.values("bfs", root)
    deep = np.isfinite(lv) & (lv >= np.nanmax(np.where(
        np.isfinite(lv), lv, np.nan)) - 1)
    cand = np.nonzero(deep[g.dst])[0]
    assert cand.size
    e = int(cand[0])
    sg.delete_edges([g.src[e]], [g.dst[e]])
    info = sg.commit()
    np.testing.assert_array_equal(
        _to_levels(sg.values("bfs", root)),
        reference.bfs_levels(sg.g, root))
    ms = info.maint[("bfs", root)]
    assert ms.messages < int(cold.messages) // 2


# --------------------------------------------------------------------------
# adaptive rhizome growth (satellite 2)
# --------------------------------------------------------------------------

def test_adaptive_split_matches_from_scratch():
    """Stream edges into one hub until its in-degree crosses the pinned
    Eq. 1 cutoff: the online split must produce (a) more replicas for
    the hub, (b) values, (c) per-round planner-mirror records and
    (d) ``with_debug`` kernel counters all exactly equal to a
    from-scratch partition of the final graph."""
    from repro import exchange, obs
    from repro.kernels.fused_relax_reduce import (
        fused_grid_cells, fused_relax_reduce_pallas)
    import jax.numpy as jnp

    g = generators.erdos_renyi(64, avg_degree=3.0, seed=6)
    hub = 7
    pcfg = PartitionConfig(num_shards=4, rpvo_max=4,
                           local_edge_list_size=8, seed=3,
                           indegree_cutoff=4)
    sg = StreamingGraph(g, pcfg)
    root = int(g.src[0])
    sg.track("bfs", root)

    def hub_replicas(part):
        return int(part.num_replicas[hub])

    r0 = hub_replicas(sg.view("base").part)
    added = 0
    rng = np.random.default_rng(2)
    while hub_replicas(sg.view("base").part) == r0:
        s = rng.integers(0, g.n, 4).astype(np.int32)
        sg.insert_edges(s, np.full(4, hub, np.int32))
        info = sg.commit()
        added += info.replicas_added
        assert added < 64, "hub never split"
    assert added >= 1
    assert hub_replicas(sg.view("base").part) > r0

    part = sg.view("base").part
    cold = build_partition(sg.g, sg.pcfg)
    _assert_parts_equal(part, cold)
    np.testing.assert_array_equal(
        _to_levels(sg.values("bfs", root)),
        reference.bfs_levels(sg.g, root))

    # post-split rounds: record stream on the spliced partition ==
    # record stream on the from-scratch partition, and each round's
    # planner mirror + kernel debug counters agree
    cfg = engine.EngineConfig(use_pallas=True, grid_mode="worklist")
    recs = {}
    for name, p in (("spliced", part), ("scratch", cold)):
        with obs.recording(keep_frontiers=True) as rec:
            init = engine.init_values(p, actions.BFS, {root: 0.0})
            engine.run_stacked(actions.BFS, p, init, cfg)
        recs[name] = rec
    a, b = recs["spliced"], recs["scratch"]
    assert len(a.rounds) == len(b.rounds) > 0
    for ra, rb in zip(a.rounds, b.rounds):
        assert (ra.messages, ra.frontier, ra.cells, ra.launched) \
            == (rb.messages, rb.frontier, rb.cells, rb.launched)
    planner = engine.launch_planner(part, cfg)
    total = part.S * part.R_max
    gval = np.random.default_rng(0).uniform(
        0.0, 5.0, total).astype(np.float32)
    for r, gchg in zip(a.rounds, a.frontiers):
        wl, info = engine.plan_round_worklist(planner, cfg, gchg,
                                              with_info=True)
        assert (r.cells, r.launched) == (info.cells, info.launched)
        _, dbg = fused_relax_reduce_pallas(
            jnp.asarray(gval), jnp.asarray(gchg),
            jnp.asarray(part.edge_src_root_flat.reshape(-1)),
            jnp.asarray(part.edge_w.reshape(-1), jnp.float32),
            jnp.asarray(part.edge_mask.reshape(-1)),
            jnp.asarray(part.edge_dst_flat.reshape(-1)),
            total, actions.BFS.relax_kind, actions.BFS.segment,
            interpret=True, worklist=wl, with_debug=True)
        assert int(dbg[0]) == r.cells


def test_pinned_cutoff_defaults_from_initial_graph():
    g = generators.rmat(6, edge_factor=6, seed=5)
    pcfg = PartitionConfig(num_shards=4, rpvo_max=4,
                           local_edge_list_size=8, seed=1)
    sg = StreamingGraph(g, pcfg)
    assert sg.pcfg.indegree_cutoff is not None
    want = max(int(np.ceil(g.in_degrees().max() / 4)), 1)
    assert sg.pcfg.indegree_cutoff == want
    # pinned config reproduces the unpinned initial partition exactly
    _assert_parts_equal(sg.view("base").part, build_partition(g, pcfg))


# --------------------------------------------------------------------------
# serving integration: mutations between ticks (tentpole wiring)
# --------------------------------------------------------------------------

def test_server_mutation_between_ticks():
    from repro.query.server import QueryServer

    g = generators.rmat(6, edge_factor=6, seed=3).with_random_weights(seed=3)
    pcfg = PartitionConfig(num_shards=4, rpvo_max=3,
                           local_edge_list_size=8, seed=9)
    sg = StreamingGraph(g, pcfg)
    srv = QueryServer(sg.view("base").part, n_lanes=4)
    sg.bind_server(srv)
    root = int(g.src[0])

    q1 = srv.submit("sssp", [root])
    srv.run()
    want = reference.sssp_dijkstra(g, root)
    fin = np.isfinite(want)
    np.testing.assert_allclose(srv.results[q1].values[fin], want[fin],
                               rtol=1e-6)

    rng = np.random.default_rng(5)
    s = rng.integers(0, g.n, 6).astype(np.int32)
    d = rng.integers(0, g.n, 6).astype(np.int32)
    sg.insert_edges(s, d, rng.integers(1, 10, 6).astype(np.float32))
    sg.commit()
    assert srv.counters["mutations"] == 1

    q2 = srv.submit("sssp", [root])
    srv.run()
    want = reference.sssp_dijkstra(sg.g, root)
    fin = np.isfinite(want)
    np.testing.assert_allclose(srv.results[q2].values[fin], want[fin],
                               rtol=1e-6)


def test_server_midflight_insert_warm_continues():
    from repro.query.server import QueryServer

    g = generators.rmat(7, edge_factor=6, seed=8)
    pcfg = PartitionConfig(num_shards=4, rpvo_max=3,
                           local_edge_list_size=8, seed=4)
    sg = StreamingGraph(g, pcfg)
    srv = QueryServer(sg.view("base").part, n_lanes=2)
    sg.bind_server(srv)
    root = int(np.argmax(g.out_degrees()))
    q = srv.submit("bfs", [root])
    srv.step()                      # in flight
    rng = np.random.default_rng(3)
    s = rng.integers(0, g.n, 5).astype(np.int32)
    d = rng.integers(0, g.n, 5).astype(np.int32)
    sg.insert_edges(s, d)
    sg.commit()                     # insert-only: lane state migrates
    srv.run()
    np.testing.assert_array_equal(
        srv.results[q].values.astype(np.int64),
        reference.bfs_levels(sg.g, root))


def test_server_midflight_delete_restarts_lane():
    from repro.query.server import QueryServer

    g = generators.rmat(7, edge_factor=6, seed=8).with_random_weights(seed=8)
    pcfg = PartitionConfig(num_shards=4, rpvo_max=3,
                           local_edge_list_size=8, seed=4)
    sg = StreamingGraph(g, pcfg)
    srv = QueryServer(sg.view("base").part, n_lanes=2)
    sg.bind_server(srv)
    root = int(np.argmax(g.out_degrees()))
    q = srv.submit("sssp", [root])
    srv.step()
    rng = np.random.default_rng(9)
    idx = rng.choice(sg.g.num_edges, 6, replace=False)
    sg.delete_edges(sg.g.src[idx], sg.g.dst[idx])
    sg.commit()                     # deletes: lane restarts cold
    srv.run()
    want = reference.sssp_dijkstra(sg.g, root)
    fin = np.isfinite(want)
    np.testing.assert_allclose(srv.results[q].values[fin], want[fin],
                               rtol=1e-6)


def test_server_cache_invalidation_modes():
    from repro.query.server import QueryServer
    from repro.serve.admission import ServeConfig

    g = generators.rmat(6, edge_factor=6, seed=3)
    pcfg = PartitionConfig(num_shards=4, rpvo_max=2,
                           local_edge_list_size=8, seed=9)
    sg = StreamingGraph(g, pcfg)
    srv = QueryServer(sg.view("base").part, n_lanes=2,
                      serve=ServeConfig(cache_size=16))
    sg.bind_server(srv, cache_invalidation="all")
    root = int(g.src[0])
    q1 = srv.submit("bfs", [root])
    srv.run()
    q2 = srv.submit("bfs", [root])
    srv.run()
    assert srv.counters["cache_hits"] >= 1
    hits_before = srv.counters["cache_hits"]
    sg.insert_edges([int(g.dst[0])], [root])
    sg.commit()
    assert srv.counters["cache_invalidations"] >= 1
    q3 = srv.submit("bfs", [root])       # must recompute, not hit
    srv.run()
    assert srv.counters["cache_hits"] == hits_before
    np.testing.assert_array_equal(
        srv.results[q3].values.astype(np.int64),
        reference.bfs_levels(sg.g, root))


# --------------------------------------------------------------------------
# flight-recorder wiring
# --------------------------------------------------------------------------

def test_commit_records_mutation_span_and_gauges():
    from repro import obs

    g = generators.rmat(6, edge_factor=5, seed=2)
    pcfg = PartitionConfig(num_shards=4, rpvo_max=2,
                           local_edge_list_size=8, seed=3)
    sg = StreamingGraph(g, pcfg)
    sg.track("bfs", int(g.src[0]))
    with obs.recording() as rec:
        sg.insert_edges([1, 2], [3, 4])
        sg.commit()
    events = rec.tracer._events
    spans = [e for e in events if e["name"] == "mutation"]
    assert len(spans) == 1
    assert spans[0]["args"]["inserts"] == 2
    text = rec.registry.render_prometheus()
    assert 'stream_mutations_total{kind="insert"} 2' in text
    assert "stream_shards_rebuilt" in text
    assert "stream_affected_vertices" in text
