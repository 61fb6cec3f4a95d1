"""Lane-batched multi-query execution (ISSUE 2 tentpole).

Covers the acceptance matrix: the laned fused kernel vs its jnp oracle
(mixed BFS/SSSP lanes via lane_unitw, sum lanes, OR-frontier chunk
bitmap), exactness — a K-query mixed batch is bit-identical to K
independent ``engine.run_stacked`` runs for both use_pallas paths,
stacked and sharded — per-lane round/message stats, converged-lane
inertness, and the lane-built apps (connected components, multi-source
BFS/SSSP, personalized PageRank) vs numpy references.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.apps import (
    batched_queries, bfs, cc, multi_source_bfs, personalized_pagerank, sssp,
)
from repro.core import actions, engine
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators, reference
from repro.kernels.fused_relax_reduce import (
    EBLK, _chunk_tables, _lane_messages, fused_relax_reduce_lanes_pallas,
)
from repro.kernels.ref import fused_relax_reduce_lanes_ref
from repro.query.lanes import (
    _lane_round_stacked, init_lane_values, run_stacked_lanes,
)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _lane_case(v, e, nseg, q, frontier_frac, seed):
    rng = np.random.default_rng(seed)
    gval = rng.uniform(0.0, 10.0, (v, q)).astype(np.float32)
    gchg = rng.random((v, q)) < frontier_frac
    unitw = (rng.random(q) < 0.5).astype(np.int32)
    src = rng.integers(0, v, e).astype(np.int32)
    w = rng.uniform(0.1, 2.0, e).astype(np.float32)
    mask = rng.random(e) < 0.9
    ids = np.sort(rng.integers(0, nseg, e).astype(np.int32))
    return tuple(jnp.asarray(x)
                 for x in (gval, gchg, unitw, src, w, mask, ids))


# --------------------------------------------------------------------------
# laned kernel vs laned oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("relax,kind", [("add_w", "min"), ("mul_w", "sum")])
@pytest.mark.parametrize("v,e,nseg,q", [
    (1, 1, 1, 1), (60, 90, 40, 3), (200, EBLK + 7, 300, 5),
])
def test_lanes_kernel_matches_ref(relax, kind, v, e, nseg, q):
    gval, gchg, unitw, src, w, mask, ids = _lane_case(
        v, e, nseg, q, 0.4, seed=e + q)
    got = fused_relax_reduce_lanes_pallas(
        gval, gchg, unitw, src, w, mask, ids, nseg, relax, kind,
        interpret=True)
    want = fused_relax_reduce_lanes_ref(
        gval, gchg, unitw, src, w, mask, ids, nseg, relax, kind)
    if kind == "min":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_lanes_kernel_converged_lane_is_inert():
    """A lane with an all-False frontier column contributes identity
    everywhere while live lanes still reduce — the per-lane convergence
    mask the server relies on."""
    gval, gchg, unitw, src, w, mask, ids = _lane_case(
        120, 2 * EBLK + 3, 150, 4, 0.5, seed=9)
    gchg = gchg.at[:, 2].set(False)           # lane 2 converged
    got, counts = fused_relax_reduce_lanes_pallas(
        gval, gchg, unitw, src, w, mask, ids, 150, "add_w", "min",
        interpret=True, with_count=True)
    got = np.asarray(got)
    assert np.all(got[:, 2] == np.inf)
    assert int(counts[2]) == 0
    live = [q for q in range(4) if q != 2]
    assert np.isfinite(got[:, live]).any()
    want = fused_relax_reduce_lanes_ref(
        gval, gchg, unitw, src, w, mask, ids, 150, "add_w", "min")
    np.testing.assert_array_equal(got, np.asarray(want))


def test_lanes_chunk_bitmap_is_or_across_lanes():
    """The frontier chunk-skip bit is the OR across active lanes: a chunk
    is dead only when no lane has a changed source in it."""
    v, q = 64, 3
    e_pad = 2 * EBLK
    rng = np.random.default_rng(3)
    src_p = jnp.asarray(rng.integers(0, v, e_pad).astype(np.int32))
    ids_p = jnp.asarray(np.sort(rng.integers(0, 50, e_pad)).astype(np.int32))
    mask = jnp.ones(e_pad, bool)
    gval = jnp.zeros((v, q), jnp.float32)
    w = jnp.ones(e_pad, jnp.float32)
    unitw = jnp.zeros(q, jnp.int32)

    def tables(gchg):
        ids, _, valid, active, counts = _lane_messages(
            gval, gchg, unitw, src_p, w, mask, ids_p, "add_w", "min",
            e_pad, 8)
        return _chunk_tables(ids, valid, active)[2], counts

    # lane 0 active only in chunk 0's sources, lane 1 only in chunk 1's
    gchg = np.zeros((v, q), bool)
    gchg[np.asarray(src_p)[:EBLK], 0] = True
    gchg[np.asarray(src_p)[EBLK:], 1] = True
    chunk_act, counts = tables(jnp.asarray(gchg))
    assert np.asarray(chunk_act).tolist() == [1, 1]   # OR keeps both live
    assert int(counts[2]) == 0                        # lane 2 fully dead
    act_dead, _ = tables(jnp.zeros((v, q), bool))
    assert np.asarray(act_dead).tolist() == [0, 0]


def test_lanes_kernel_rejects_non_absorbing_pairing():
    gval, gchg, unitw, src, w, mask, ids = _lane_case(
        30, 50, 20, 2, 0.5, seed=1)
    with pytest.raises(ValueError, match="non-absorbing"):
        fused_relax_reduce_lanes_pallas(
            gval, gchg, unitw, src, w, mask, ids, 20, "add_w", "sum",
            interpret=True)


# --------------------------------------------------------------------------
# exactness: K-lane mixed batch == K independent run_stacked runs
# --------------------------------------------------------------------------

def _mixed_workload(seed=4):
    g = generators.rmat(8, edge_factor=4, seed=seed).with_random_weights(
        seed=seed)
    deg = np.argsort(-g.out_degrees())
    roots = [int(deg[i]) for i in (0, 1, 2, 7)]
    queries = [("bfs", roots[0]), ("sssp", roots[1]),
               ("bfs", roots[2]), ("sssp", roots[3])]
    return g, queries


@pytest.mark.parametrize("use_pallas", [False, True])
def test_lane_batch_bit_identical_to_solo_stacked(use_pallas):
    g, queries = _mixed_workload()
    cfg = engine.EngineConfig(use_pallas=use_pallas)
    res, stats, part = batched_queries(g, queries, num_shards=4, rpvo_max=2,
                                       cfg=cfg)
    for q, ((kind, root), got) in enumerate(zip(queries, res)):
        solo_fn = bfs if kind == "bfs" else sssp
        solo, solo_stats, _ = solo_fn(g, root, part=part, cfg=cfg)
        np.testing.assert_array_equal(got, solo)    # bit-identical (min)
        # per-lane stats == the solo run's Fig-6 counters
        assert int(stats.rounds[q]) == int(solo_stats.iterations)
        assert int(stats.messages[q]) == int(solo_stats.messages)
        ref = (reference.bfs_levels(g, root) if kind == "bfs"
               else reference.sssp_dijkstra(g, root))
        if kind == "bfs":
            np.testing.assert_array_equal(got, ref)
        else:
            finite = np.isfinite(ref)
            np.testing.assert_allclose(got[finite], ref[finite], rtol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_lane_batch_sharded_matches_stacked(use_pallas):
    """Laned shard_map on the trivial 1-device mesh == the stacked laned
    run (the real 8-device check runs in the subprocess test below)."""
    from jax.sharding import Mesh
    g, queries = _mixed_workload(seed=6)
    cfg = engine.EngineConfig(use_pallas=use_pallas)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    res_sh, st_sh, part = batched_queries(g, queries, num_shards=1,
                                          rpvo_max=2, mesh=mesh, cfg=cfg)
    res_st, st_st, _ = batched_queries(g, queries, part=part, cfg=cfg)
    for a, b in zip(res_sh, res_st):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(st_sh.rounds),
                                  np.asarray(st_st.rounds))
    np.testing.assert_array_equal(np.asarray(st_sh.messages),
                                  np.asarray(st_st.messages))


CHILD_LANES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro.apps import batched_queries
    from repro.core import engine
    from repro.graph import generators

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    g = generators.rmat(8, edge_factor=4, seed=6).with_random_weights(seed=6)
    deg = np.argsort(-g.out_degrees())
    queries = [("bfs", int(deg[0])), ("sssp", int(deg[1])),
               ("bfs", int(deg[2])), ("sssp", int(deg[7]))]
    for use_pallas in (False, True):
        cfg = engine.EngineConfig(use_pallas=use_pallas)
        sh, st_sh, part = batched_queries(g, queries, num_shards=8,
                                          rpvo_max=4, mesh=mesh, cfg=cfg)
        st, st_st, _ = batched_queries(g, queries, part=part, cfg=cfg)
        for a, b in zip(sh, st):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(np.asarray(st_sh.rounds),
                                      np.asarray(st_st.rounds))
        np.testing.assert_array_equal(np.asarray(st_sh.messages),
                                      np.asarray(st_st.messages))
    print("LANES_SHARDED_OK")
""")


def test_lane_batch_eight_devices_subprocess():
    """Laned fixpoint under real 8-device shard_map collectives equals
    the stacked laned run, jnp and fused."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    # pin the child to CPU: with libtpu present, backend autodetect stalls
    # on (unreachable) TPU metadata; these are CPU host devices
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", CHILD_LANES], env=env, capture_output=True,
        text=True, timeout=420)
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr}"
    assert "LANES_SHARDED_OK" in out.stdout


def test_converged_lane_stays_frozen_across_extra_rounds():
    """Drive the laned round past one lane's convergence: the converged
    column must stay bit-stable while the other lane keeps relaxing."""
    g = generators.ring(64).with_random_weights(seed=0)
    part = build_partition(g, PartitionConfig(num_shards=4, rpvo_max=1))
    # lane 0: seeded one hop from the wrap point -> converges in ~2 rounds?
    # on a directed ring every BFS takes n-1 rounds; instead make lane 0
    # converge instantly by seeding EVERY vertex at 0 (no improvement
    # possible), lane 1 a genuine BFS from vertex 0
    init, unitw = init_lane_values(
        part, [("bfs", {v: 0.0 for v in range(64)}), ("bfs", 0)])
    arrays = engine.DeviceArrays.from_partition(part)
    val = jnp.asarray(init)
    chg = actions.SSSP.improved(val, jnp.full_like(val, jnp.inf)) \
        & arrays.slot_valid[..., None]
    cfg = engine.EngineConfig(use_pallas=True)
    frozen = None
    for rnd in range(6):
        val, chg, _ = _lane_round_stacked(
            actions.SSSP, arrays, cfg, part.S, part.R_max,
            jnp.asarray(unitw), val, chg)
        lane0_live = bool(np.asarray(chg)[..., 0].any())
        if rnd == 0:
            assert not lane0_live        # all-zero seed converges round 1
            frozen = np.asarray(val)[..., 0].copy()
        else:
            np.testing.assert_array_equal(np.asarray(val)[..., 0], frozen)
            assert bool(np.asarray(chg)[..., 1].any())   # ring BFS still live
    assert frozen is not None


def test_lane_runner_rejects_unsupported_configs():
    g = generators.ring(16)
    part = build_partition(g, PartitionConfig(num_shards=2))
    init = np.full((part.S, part.R_max, 1), np.inf, np.float32)
    with pytest.raises(ValueError, match="eager"):
        run_stacked_lanes(part, init,
                          cfg=engine.EngineConfig(collapse="deferred"))
    with pytest.raises(ValueError, match="fused-only"):
        run_stacked_lanes(part, init,
                          cfg=engine.EngineConfig(use_pallas=True,
                                                  pallas_mode="reduce"))
    with pytest.raises(ValueError, match="min-semiring"):
        run_stacked_lanes(part, init, sem=actions.PAGERANK)
    # the BFS semiring's own relax is 'add_one', which the laned round
    # (hardcoded 'add_w' + lane_unitw) would silently mis-execute on a
    # weighted graph — it must be rejected, BFS lanes use lane_unitw=1
    with pytest.raises(ValueError, match="lane_unitw"):
        run_stacked_lanes(part, init, sem=actions.BFS)
    with pytest.raises(ValueError, match=r"\(S, R_max, Q\)"):
        run_stacked_lanes(part, init[..., 0])


# --------------------------------------------------------------------------
# lane-built apps vs numpy references
# --------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True])
def test_connected_components_matches_reference(use_pallas):
    g = generators.erdos_renyi(220, avg_degree=2.0, seed=11)
    labels, stats, _ = cc(g, num_shards=4, rpvo_max=2,
                          cfg=engine.EngineConfig(use_pallas=use_pallas))
    np.testing.assert_array_equal(labels, reference.connected_components(g))
    assert int(stats.rounds[0]) > 1


def test_connected_components_disconnected_graph():
    """Two disjoint rings -> two labels (the min vertex id of each)."""
    from repro.graph.graph import COOGraph
    r = 20
    src = np.concatenate([np.arange(r), np.arange(r) + r])
    dst = np.concatenate([(np.arange(r) + 1) % r,
                          (np.arange(r) + 1) % r + r]).astype(np.int32)
    g = COOGraph(2 * r, src.astype(np.int32), dst, None)
    labels, _, _ = cc(g, num_shards=4)
    assert set(labels[:r]) == {0} and set(labels[r:]) == {r}


def test_multi_source_bfs_is_min_over_solo_runs():
    g = generators.rmat(8, edge_factor=4, seed=13)
    deg = np.argsort(-g.out_degrees())
    roots = [int(deg[0]), int(deg[3]), int(deg[9])]
    got, _, _ = multi_source_bfs(g, roots, num_shards=4)
    solo = np.stack([reference.bfs_levels(g, r) for r in roots])
    np.testing.assert_array_equal(got, solo.min(axis=0))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_personalized_pagerank_lanes_match_reference(use_pallas):
    g = generators.rmat(7, edge_factor=5, seed=3)
    deg = np.argsort(-g.out_degrees())
    seeds = [int(deg[0]), int(deg[2])]
    dampings = [0.85, 0.6]
    scores, stats, _ = personalized_pagerank(
        g, seeds, dampings, num_shards=4, rpvo_max=2, tol=1e-9,
        cfg=engine.EngineConfig(use_pallas=use_pallas))
    for q, (s, d) in enumerate(zip(seeds, dampings)):
        want = reference.personalized_pagerank(g, s, d, tol=1e-12)
        np.testing.assert_allclose(scores[:, q], want, rtol=1e-4, atol=1e-7)
    # the lower-damping lane contracts faster -> strictly fewer rounds
    assert int(stats.rounds[1]) <= int(stats.rounds[0])
