"""Delta-PageRank tests (ISSUE 5 tentpole: diffusion-pruned sum semiring).

Push-based residual propagation must converge to the numpy PageRank
reference on every execution path (stacked / sharded / laned-PPR, jnp /
fused / worklist / compact), and must do strictly less work than the
dense power iteration — fewer messages AND fewer live grid cells — the
first time the frontier machinery fires for the sum semiring.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.apps.pagerank import _pr_graph, pagerank, pagerank_delta
from repro.core import actions, engine
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators, reference
from repro.kernels.fused_relax_reduce import fused_grid_cells
from repro.query.lanes import run_ppr_delta_lanes


@pytest.fixture(scope="module")
def rmat_graph():
    return generators.rmat(8, edge_factor=6, seed=3)


@pytest.fixture(scope="module")
def rmat_reference(rmat_graph):
    return reference.pagerank(rmat_graph, iters=200)


CONFIGS = [
    ("jnp", engine.EngineConfig()),
    ("fused", engine.EngineConfig(use_pallas=True)),
    ("fused-worklist", engine.EngineConfig(use_pallas=True,
                                           grid_mode="worklist")),
    ("fused-auto", engine.EngineConfig(use_pallas=True, grid_mode="auto")),
    ("compact", engine.EngineConfig(exchange="compact")),
    ("compact-fused-wl", engine.EngineConfig(
        exchange="compact", use_pallas=True, grid_mode="worklist")),
    ("fused-device-wl", engine.EngineConfig(
        use_pallas=True, grid_mode="device_worklist")),
]


@pytest.mark.parametrize("label,cfg", CONFIGS)
def test_delta_converges_to_reference(rmat_graph, rmat_reference, label,
                                      cfg):
    scores, stats, _ = pagerank_delta(rmat_graph, tol=1e-9, num_shards=8,
                                      rpvo_max=4, cfg=cfg, max_rounds=400)
    np.testing.assert_allclose(scores, rmat_reference, rtol=1e-4,
                               atol=1e-7)
    assert int(stats.iterations) > 0
    assert int(stats.messages) > 0
    assert int(stats.pruned_actions) > 0     # sub-tol residuals dropped


def test_delta_matches_dense_pagerank(rmat_graph):
    dense, _ = pagerank(rmat_graph, iters=100, num_shards=8, rpvo_max=4)
    delta, _, _ = pagerank_delta(rmat_graph, tol=1e-10, num_shards=8,
                                 rpvo_max=4, max_rounds=400)
    np.testing.assert_allclose(delta, dense, rtol=1e-4, atol=1e-8)


def test_delta_paths_agree_exactly_on_stats(rmat_graph):
    """Every grid mode prunes identically: same rounds, messages, work —
    the launch shape is an optimization, never a semantics change."""
    ref_stats = None
    for label, cfg in CONFIGS:
        _, stats, _ = pagerank_delta(rmat_graph, tol=1e-9, num_shards=8,
                                     rpvo_max=4, cfg=cfg, max_rounds=400)
        row = (int(stats.iterations), int(stats.messages),
               int(stats.work_actions), int(stats.pruned_actions))
        if ref_stats is None:
            ref_stats = row
        assert row == ref_stats, (label, row, ref_stats)


def test_delta_sharded_matches_stacked(rmat_graph, rmat_reference):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    cfg = engine.EngineConfig(use_pallas=True)
    st_scores, st_stats, part = pagerank_delta(
        rmat_graph, tol=1e-9, num_shards=1, cfg=cfg, max_rounds=400)
    sh_scores, sh_stats, _ = pagerank_delta(
        rmat_graph, tol=1e-9, num_shards=1, part=part, mesh=mesh, cfg=cfg,
        max_rounds=400)
    np.testing.assert_allclose(sh_scores, st_scores, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(sh_scores, rmat_reference, rtol=1e-4,
                               atol=1e-7)
    assert int(sh_stats.iterations) == int(st_stats.iterations)
    assert int(sh_stats.messages) == int(st_stats.messages)


def test_delta_per_vertex_tolerance(rmat_graph, rmat_reference):
    """A per-vertex tol array is honored: uniform array == scalar, and a
    cranked-up tolerance on half the graph prunes more (fewer messages)
    while still bounding those vertices' error by the larger tol."""
    n = rmat_graph.n
    sc_scalar, st_scalar, part = pagerank_delta(
        rmat_graph, tol=1e-7, num_shards=8, rpvo_max=4, max_rounds=400)
    sc_arr, st_arr, _ = pagerank_delta(
        rmat_graph, tol=np.full(n, 1e-7, np.float32), part=part,
        max_rounds=400)
    np.testing.assert_array_equal(sc_arr, sc_scalar)
    assert int(st_arr.messages) == int(st_scalar.messages)
    mixed = np.full(n, 1e-7, np.float32)
    mixed[n // 2:] = 1e-3
    sc_mix, st_mix, _ = pagerank_delta(rmat_graph, tol=mixed, part=part,
                                       max_rounds=400)
    assert int(st_mix.messages) < int(st_scalar.messages)
    np.testing.assert_allclose(sc_mix, rmat_reference, atol=2e-2)


def test_delta_prunes_messages_and_cells_vs_dense(rmat_graph):
    """The ISSUE-5 acceptance bar: on the RMAT graph, delta-PageRank
    executes strictly fewer messages AND strictly fewer live grid cells
    than the same number of dense PageRank rounds — the frontier
    machinery finally bites for the sum semiring."""
    part = build_partition(_pr_graph(rmat_graph),
                           PartitionConfig(num_shards=8, rpvo_max=4))
    arrays = engine.DeviceArrays.from_partition(part)
    sem = actions.PAGERANK
    cfg = engine.EngineConfig(use_pallas=True)
    total = part.S * part.R_max
    damping, rounds_n = 0.85, 18

    # dense rounds: frontier is every valid slot, every round
    full = np.asarray(arrays.slot_valid).reshape(-1)
    dense_cells_round = fused_grid_cells(
        part.edge_dst_flat, part.edge_mask, part.edge_src_root_flat,
        full, total)["fused_live"]
    base = (1.0 - damping) / part.n
    val = jnp.where(arrays.slot_valid, 1.0 / part.n, 0.0)
    dense_msgs = 0
    for _ in range(rounds_n):
        val, mc = engine._pagerank_round_stacked(
            sem, arrays, cfg, part.S, part.R_max, base, damping, val,
            jnp.asarray(arrays.slot_valid))
        dense_msgs += int(mc)
    dense_cells = dense_cells_round * rounds_n

    # delta rounds: residual frontier shrinks (tol picked so the RMAT
    # residuals decay through it within the round budget — ~0.85^k decay
    # from base=(1-d)/n)
    tol = jnp.asarray(1e-5, jnp.float32)
    rank = delta = jnp.where(arrays.slot_valid, base, 0.0)
    delta_msgs = delta_cells = it = 0
    while it < rounds_n:
        chg_h = np.asarray((delta > tol) & arrays.slot_valid)
        if not chg_h.any():
            break
        delta_cells += fused_grid_cells(
            part.edge_dst_flat, part.edge_mask, part.edge_src_root_flat,
            chg_h.reshape(-1), total)["fused_live"]
        rank, delta, _, mc = engine.exchange.delta_pagerank_round_stacked(
            sem, arrays, cfg, part.S, part.R_max, damping, tol, rank,
            delta)
        delta_msgs += int(mc)
        it += 1
    assert delta_msgs < dense_msgs, (delta_msgs, dense_msgs)
    assert delta_cells < dense_cells, (delta_cells, dense_cells)


def test_delta_max_rounds_cap(rmat_graph):
    _, stats, _ = pagerank_delta(rmat_graph, tol=1e-12, num_shards=8,
                                 rpvo_max=4, max_rounds=3)
    assert int(stats.iterations) == 3


def test_ppr_delta_lanes_match_reference():
    g = generators.ba_skewed(200, m_per=3, seed=4)
    part = build_partition(_pr_graph(g),
                           PartitionConfig(num_shards=4, rpvo_max=4))
    seeds = [7, 23, 101]
    dampings = [0.85, 0.9, 0.85]
    for cfg in (engine.EngineConfig(),
                engine.EngineConfig(use_pallas=True,
                                    grid_mode="worklist"),
                engine.EngineConfig(exchange="compact", use_pallas=True)):
        scores, stats = run_ppr_delta_lanes(
            part, seeds, dampings, cfg=cfg, tol=1e-10, max_rounds=500)
        vv = np.asarray(scores).reshape(-1, len(seeds))
        for i, (s, d) in enumerate(zip(seeds, dampings)):
            ref = reference.personalized_pagerank(g, s, d, tol=1e-12)
            got = vv[:, i][part.root_flat]
            np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-7)
            assert int(stats.rounds[i]) > 0


def test_ppr_delta_lanes_prune_vs_full_rounds():
    from repro.query.lanes import run_ppr_lanes
    g = generators.rmat(8, edge_factor=6, seed=3)
    part = build_partition(_pr_graph(g),
                           PartitionConfig(num_shards=4, rpvo_max=4))
    seeds = [3, 50]
    cfg = engine.EngineConfig(use_pallas=True)
    _, st_full = run_ppr_lanes(part, seeds, 0.85, cfg=cfg, tol=1e-8,
                               max_rounds=200)
    _, st_delta = run_ppr_delta_lanes(part, seeds, 0.85, cfg=cfg,
                                      tol=1e-8, max_rounds=200)
    assert (np.asarray(st_delta.messages)
            < np.asarray(st_full.messages)).all()


# --------------------------------------------------------------------------
# the resident partition: tables uploaded once, round programs compiled once
# --------------------------------------------------------------------------

RESIDENT_GRAPHS = {
    "rmat9": lambda: generators.rmat(9, edge_factor=8, seed=11),
    "er9": lambda: generators.erdos_renyi(512, avg_degree=8.0, seed=12),
}


def _resident(g, grid_mode):
    part = build_partition(_pr_graph(g),
                           PartitionConfig(num_shards=4, rpvo_max=4))
    cfg = engine.EngineConfig(use_pallas=True, grid_mode=grid_mode)
    return engine.ResidentPartition(part), cfg


def _tol(g, rel=1e-5, damping=0.85):
    return rel * (1.0 - damping) / g.n


def _round_programs(run):
    from repro import obs
    return obs.registry().counter(
        "engine_round_programs_total").labels(run=run).value


@pytest.fixture
def backend_compiles():
    """Every backend compile JAX reports while the test runs."""
    seen = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)
    jax.monitoring.register_event_duration_secs_listener(listen)
    yield seen
    jax.monitoring.unregister_event_duration_listener(listen)


@pytest.mark.parametrize("grid_mode", ["worklist", "dense"])
@pytest.mark.parametrize("graph", list(RESIDENT_GRAPHS))
def test_resident_delta_matches_float64_reference(graph, grid_mode):
    g = RESIDENT_GRAPHS[graph]()
    res, cfg = _resident(g, grid_mode)
    want, rounds = reference.pagerank_delta(g, 0.85, _tol(g))
    got, stats, out = pagerank_delta(g, tol=_tol(g), part=res, cfg=cfg)
    assert out is res
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)
    assert abs(int(stats.iterations) - rounds) <= 1, (stats, rounds)


@pytest.mark.parametrize("grid_mode", ["worklist", "dense"])
def test_second_delta_call_on_resident_compiles_nothing(grid_mode,
                                                        backend_compiles):
    g = RESIDENT_GRAPHS["rmat9"]()
    res, cfg = _resident(g, grid_mode)
    before = _round_programs("pagerank_delta")
    first, st1, _ = pagerank_delta(g, tol=_tol(g), part=res, cfg=cfg)
    traced = _round_programs("pagerank_delta")
    assert traced > before and backend_compiles
    compiles = len(backend_compiles)
    second, st2, _ = pagerank_delta(g, tol=_tol(g), part=res, cfg=cfg)
    assert _round_programs("pagerank_delta") == traced
    assert len(backend_compiles) == compiles
    np.testing.assert_array_equal(second, first)
    assert tuple(map(int, st2)) == tuple(map(int, st1))


def test_plain_and_resident_partitions_agree_bit_for_bit():
    g = RESIDENT_GRAPHS["er9"]()
    res, cfg = _resident(g, "worklist")
    plain, st_plain, _ = pagerank_delta(g, tol=_tol(g), part=res.part,
                                        cfg=cfg)
    kept, st_kept, _ = pagerank_delta(g, tol=_tol(g), part=res, cfg=cfg)
    np.testing.assert_array_equal(kept, plain)
    assert tuple(map(int, st_kept)) == tuple(map(int, st_plain))


def test_second_bfs_call_on_resident_compiles_nothing(backend_compiles):
    from repro import apps
    g = generators.rmat(9, edge_factor=8, seed=5)
    part = build_partition(g, PartitionConfig(num_shards=4, rpvo_max=4))
    res = engine.ResidentPartition(part)
    cfg = engine.EngineConfig(use_pallas=True, grid_mode="worklist")
    root = int(np.argmax(g.out_degrees()))
    first, st1, _ = apps.bfs(g, root, part=res, cfg=cfg)
    traced = _round_programs("bfs")
    compiles = len(backend_compiles)
    second, st2, _ = apps.bfs(g, root, part=res, cfg=cfg)
    assert _round_programs("bfs") == traced
    assert len(backend_compiles) == compiles
    np.testing.assert_array_equal(second, first)
    np.testing.assert_array_equal(first, reference.bfs_levels(g, root))
    assert int(st2.iterations) == int(st1.iterations)
