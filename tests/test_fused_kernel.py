"""Fused frontier relax+reduce kernel vs oracles, and the engine hot path.

Covers the ISSUE-1 acceptance matrix: kernel parity vs the jnp oracle
across semirings / frontier densities / padding / non-block-multiple
shapes, engine equivalence (use_pallas=True vs the jnp path) for
BFS/SSSP/PageRank under dense and compact exchange in run_stacked and
run_sharded, and the frontier chunk-skip actually firing on late sparse
rounds.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.apps import bfs, sssp, pagerank
from repro.core import actions, engine
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators, reference
from repro.kernels.fused_relax_reduce import (
    EBLK, SBLK, fused_grid_cells, fused_relax_reduce_pallas,
)
from repro.kernels.ref import fused_relax_reduce_ref


def _case(v, e, nseg, frontier_frac, seed, sorted_ids=True):
    rng = np.random.default_rng(seed)
    gval = rng.uniform(0.0, 10.0, v).astype(np.float32)
    gchg = rng.random(v) < frontier_frac
    src = rng.integers(0, v, e).astype(np.int32)
    w = rng.uniform(0.1, 2.0, e).astype(np.float32)
    mask = rng.random(e) < 0.9
    ids = rng.integers(0, nseg, e).astype(np.int32)
    if sorted_ids:
        ids = np.sort(ids)
    return tuple(jnp.asarray(x) for x in (gval, gchg, src, w, mask, ids))


SHAPES = [
    (1, 1, 1), (17, 7, 3), (200, 100, 17),
    (300, EBLK, SBLK), (130, EBLK + 1, SBLK + 1),
    (500, 2 * EBLK + 13, 2 * SBLK + 5), (64, EBLK - 1, 1000),
]


@pytest.mark.parametrize("relax,kind", [
    ("add_w", "min"), ("add_one", "min"), ("mul_w", "sum")])
@pytest.mark.parametrize("v,e,nseg", SHAPES)
def test_fused_matches_ref(relax, kind, v, e, nseg):
    gval, gchg, src, w, mask, ids = _case(v, e, nseg, 0.4, seed=e + nseg)
    got = fused_relax_reduce_pallas(gval, gchg, src, w, mask, ids, nseg,
                                    relax, kind, interpret=True)
    want = fused_relax_reduce_ref(gval, gchg, src, w, mask, ids, nseg,
                                  relax, kind)
    if kind == "min":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("relax,kind", [("add_w", "min"), ("mul_w", "sum")])
@pytest.mark.parametrize("frontier_frac", [0.0, 0.05, 1.0])
def test_fused_frontier_densities(relax, kind, frontier_frac):
    """Empty, sparse, and full frontiers all reduce correctly — the chunk
    skip must never drop a live contribution."""
    gval, gchg, src, w, mask, ids = _case(400, 3 * EBLK + 9, 700,
                                          frontier_frac, seed=5)
    got = fused_relax_reduce_pallas(gval, gchg, src, w, mask, ids, 700,
                                    relax, kind, interpret=True)
    want = fused_relax_reduce_ref(gval, gchg, src, w, mask, ids, 700,
                                  relax, kind)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    if frontier_frac == 0.0:
        identity = np.inf if kind == "min" else 0.0
        assert np.all(np.asarray(got) == identity)


@pytest.mark.parametrize("kind", ["min", "sum"])
def test_fused_padding_edges_inert(kind):
    """Masked-off (padding) edges never contribute, whatever their ids."""
    relax = "add_w" if kind == "min" else "mul_w"
    gval = jnp.asarray(np.arange(10, dtype=np.float32))
    gchg = jnp.ones(10, bool)
    src = jnp.asarray([0, 1, 2, 3], jnp.int32)
    w = jnp.ones(4, jnp.float32)
    mask = jnp.asarray([True, True, False, False])
    ids = jnp.asarray([2, 2, 0, 5], jnp.int32)   # padding aimed at live segs
    got = np.asarray(fused_relax_reduce_pallas(
        gval, gchg, src, w, mask, ids, 6, relax, kind, interpret=True))
    identity = np.inf if kind == "min" else 0.0
    expect0 = identity          # only padding pointed at segment 0
    expect5 = identity
    assert got[0] == expect0 and got[5] == expect5
    if kind == "min":
        assert got[2] == 1.0    # min(0+1, 1+1)
    else:
        assert got[2] == 1.0    # 0*1 + 1*1


def test_fused_rejects_non_absorbing_pairing():
    """Frontier masking relies on relax(identity, w) == identity; pairings
    without that property must be rejected, not silently mis-summed."""
    gval, gchg, src, w, mask, ids = _case(50, 100, 40, 0.5, seed=3)
    with pytest.raises(ValueError, match="non-absorbing"):
        fused_relax_reduce_pallas(gval, gchg, src, w, mask, ids, 40,
                                  "add_w", "sum", interpret=True)


def test_fixpoint_runners_reject_sum_semirings():
    """run_stacked/make_sharded_fn collapse combined candidates — only
    sound for min semirings; sum semirings must be routed to the PageRank
    runners instead of silently double-counting sibling values."""
    g = generators.ring(32)
    from repro.core.partition import PartitionConfig, build_partition
    part = build_partition(g, PartitionConfig(num_shards=2))
    init = engine.init_values(part, actions.PAGERANK, {})
    with pytest.raises(ValueError, match="min-semiring"):
        engine.run_stacked(actions.PAGERANK, part, init)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="min-semiring"):
        engine.make_sharded_fn(actions.PAGERANK, part.S, part.R_max, mesh)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_track_stats_off_is_consistent(use_pallas):
    """track_stats=False zeroes the message/pruned counters identically on
    the fused and jnp paths (values unaffected)."""
    g = generators.erdos_renyi(150, avg_degree=4.0, seed=2)
    root = int(g.src[0])
    on, s_on, _ = bfs(g, root, num_shards=4,
                      cfg=engine.EngineConfig(use_pallas=use_pallas))
    off, s_off, _ = bfs(g, root, num_shards=4,
                        cfg=engine.EngineConfig(use_pallas=use_pallas,
                                                track_stats=False))
    np.testing.assert_array_equal(off, on)
    assert int(s_on.messages) > 0
    assert int(s_off.messages) == 0 and int(s_off.pruned_actions) == 0
    assert int(s_off.iterations) == int(s_on.iterations)


def test_fused_unsorted_ids_still_correct():
    """The range skip is an optimization over sorted dsts; correctness must
    hold for arbitrary id order."""
    gval, gchg, src, w, mask, ids = _case(300, 1000, 400, 0.5, seed=11,
                                          sorted_ids=False)
    got = fused_relax_reduce_pallas(gval, gchg, src, w, mask, ids, 400,
                                    "add_w", "min", interpret=True)
    want = fused_relax_reduce_ref(gval, gchg, src, w, mask, ids, 400,
                                  "add_w", "min")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------
# engine equivalence: use_pallas=True vs the jnp oracle path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("exchange", ["dense", "compact"])
def test_engine_stacked_pallas_matches_jnp(exchange):
    g = generators.ba_skewed(260, m_per=4, seed=9).with_random_weights(seed=9)
    root = int(np.argmax(g.out_degrees()))
    cfg_j = engine.EngineConfig(exchange=exchange)
    cfg_p = engine.EngineConfig(exchange=exchange, use_pallas=True)

    lv_j, st_j, _ = bfs(g, root, num_shards=8, rpvo_max=4, cfg=cfg_j)
    lv_p, st_p, _ = bfs(g, root, num_shards=8, rpvo_max=4, cfg=cfg_p)
    np.testing.assert_array_equal(lv_j, reference.bfs_levels(g, root))
    np.testing.assert_array_equal(lv_p, lv_j)          # bit-identical (min)
    assert int(st_p.messages) == int(st_j.messages)
    assert int(st_p.pruned_actions) == int(st_j.pruned_actions)

    d_j, _, _ = sssp(g, root, num_shards=8, rpvo_max=4, cfg=cfg_j)
    d_p, _, _ = sssp(g, root, num_shards=8, rpvo_max=4, cfg=cfg_p)
    np.testing.assert_array_equal(d_p, d_j)            # bit-identical (min)

    pr_j, _ = pagerank(g, iters=15, num_shards=8, rpvo_max=4, cfg=cfg_j)
    pr_p, _ = pagerank(g, iters=15, num_shards=8, rpvo_max=4, cfg=cfg_p)
    np.testing.assert_allclose(pr_j, reference.pagerank(g, iters=15),
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(pr_p, pr_j, rtol=1e-5, atol=1e-9)


def test_engine_compact_sum_semiring_matches_dense():
    """The compact targeted exchange now carries the sum semiring: compact
    PageRank must agree with the dense path and the numpy oracle."""
    g = generators.rmat(8, edge_factor=6, seed=3)
    pr_dense, _ = pagerank(g, iters=20, num_shards=8, rpvo_max=4,
                           cfg=engine.EngineConfig(exchange="dense"))
    pr_comp, _ = pagerank(g, iters=20, num_shards=8, rpvo_max=4,
                          cfg=engine.EngineConfig(exchange="compact"))
    np.testing.assert_allclose(pr_comp, reference.pagerank(g, iters=20),
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(pr_comp, pr_dense, rtol=1e-5, atol=1e-9)


def test_engine_stacked_vs_sharded_pallas():
    """use_pallas=True on the trivial 1-device mesh == stacked fused run."""
    from jax.sharding import Mesh
    g = generators.erdos_renyi(180, avg_degree=4.0, seed=21)
    root = int(g.src[0])
    cfg = engine.EngineConfig(use_pallas=True)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    lv_st, _, _ = bfs(g, root, num_shards=1, cfg=cfg)
    lv_sh, _, _ = bfs(g, root, num_shards=1, mesh=mesh, cfg=cfg)
    np.testing.assert_array_equal(lv_sh, lv_st)
    np.testing.assert_array_equal(lv_st, reference.bfs_levels(g, root))


# --------------------------------------------------------------------------
# frontier chunk-skip: late sparse rounds execute fewer grid cells
# --------------------------------------------------------------------------

def test_frontier_skip_fires_on_late_rounds():
    """Drive BFS round-by-round on a long path (ring): the frontier is one
    vertex per round, so the fused kernel must skip grid cells that the
    range-skip alone (the unfused reduce kernel) would execute. The ring is
    sized to several EBLK edge chunks so dead chunks exist to skip."""
    g = generators.ring(4 * EBLK)
    part = build_partition(g, PartitionConfig(num_shards=4, rpvo_max=1))
    sem = actions.BFS
    arrays = engine.DeviceArrays.from_partition(part)
    init = engine.init_values(part, sem, {0: 0.0})
    val_p = val_j = jnp.asarray(init)
    chg0 = sem.improved(val_p, jnp.full_like(val_p, sem.identity)) \
        & arrays.slot_valid
    chg_p = chg_j = chg0
    cfg_p = engine.EngineConfig(use_pallas=True)
    cfg_j = engine.EngineConfig(use_pallas=False)
    total = part.S * part.R_max

    rounds = []
    for _ in range(10):
        cells = fused_grid_cells(part.edge_dst_flat, part.edge_mask,
                                 part.edge_src_root_flat,
                                 np.asarray(chg_p).reshape(-1), total)
        rounds.append(cells)
        val_p, chg_p, _ = engine._fixpoint_round_stacked(
            sem, arrays, cfg_p, part.S, part.R_max, val_p, chg_p)
        val_j, chg_j, _ = engine._fixpoint_round_stacked(
            sem, arrays, cfg_j, part.S, part.R_max, val_j, chg_j)
        # the skip is exact, never lossy: fused == oracle every round
        np.testing.assert_array_equal(np.asarray(val_p), np.asarray(val_j))
        np.testing.assert_array_equal(np.asarray(chg_p), np.asarray(chg_j))
    late = rounds[-1]
    assert late["fused_live"] < late["range_live"], rounds
    assert all(r["fused_live"] <= r["range_live"] for r in rounds)


@pytest.mark.parametrize("relax,kind", [
    ("add_w", "min"), ("add_one", "min"), ("mul_w", "sum")])
@pytest.mark.parametrize("v,e,nseg", SHAPES)
def test_grid_cell_dma_oracle_matches_kernel(relax, kind, v, e, nseg):
    """The host-side ``fused_grid_cells`` mirror must EXACTLY match the
    kernel-side executed-cell counter (``with_debug``) on every
    kernel-parity case, for the dense grid and the worklist launch
    alike.  (The launches issue no manual DMA: the value gather runs in
    XLA ahead of the kernel.)"""
    gval, gchg, src, w, mask, ids = _case(v, e, nseg, 0.4, seed=e + nseg)
    for mode, key in (("dense", "fused_live"), ("worklist", "wl_cells")):
        mirror = fused_grid_cells(np.asarray(ids), np.asarray(mask),
                                  np.asarray(src), np.asarray(gchg), nseg,
                                  grid_mode=mode)
        _, dbg = fused_relax_reduce_pallas(
            gval, gchg, src, w, mask, ids, nseg, relax, kind,
            interpret=True, grid_mode=mode, with_debug=True)
        assert dbg.shape == (1,)
        assert int(dbg[0]) == mirror[key], (mode, int(dbg[0]), mirror)


@pytest.mark.parametrize("frontier_frac", [0.0, 0.05, 1.0])
def test_grid_cell_dma_oracle_matches_kernel_lanes(frontier_frac):
    """Laned launch oracle: the mirror over the OR-across-lanes frontier
    matches the laned kernels' executed-cell counter."""
    from repro.kernels.fused_relax_reduce import (
        fused_relax_reduce_lanes_pallas,
    )
    rng = np.random.default_rng(3)
    v, e, nseg, q = 300, 2 * EBLK + 7, 500, 3
    gval = jnp.asarray(rng.uniform(0, 10, (v, q)).astype(np.float32))
    gchg = jnp.asarray(rng.random((v, q)) < frontier_frac)
    unitw = jnp.asarray([1, 0, 1], jnp.int32)
    src = jnp.asarray(rng.integers(0, v, e).astype(np.int32))
    w = jnp.asarray(rng.uniform(0.1, 2, e).astype(np.float32))
    mask = jnp.asarray(rng.random(e) < 0.9)
    ids = jnp.asarray(np.sort(rng.integers(0, nseg, e)).astype(np.int32))
    for mode, key in (("dense", "fused_live"), ("worklist", "wl_cells")):
        mirror = fused_grid_cells(np.asarray(ids), np.asarray(mask),
                                  np.asarray(src),
                                  np.asarray(gchg).any(axis=-1), nseg,
                                  grid_mode=mode)
        _, dbg = fused_relax_reduce_lanes_pallas(
            gval, gchg, unitw, src, w, mask, ids, nseg, "add_w", "min",
            interpret=True, grid_mode=mode, with_debug=True)
        assert int(dbg[0]) == mirror[key], (mode, int(dbg[0]), mirror)


def test_grid_cell_counter_matches_kernel_semantics():
    """fused_grid_cells mirrors the launch predicates: a dead frontier
    yields zero live fused cells; a full frontier can never beat the
    unfused range-skip count by more than the mask-aware ranges allow."""
    gval, gchg, src, w, mask, ids = _case(300, 2000, 500, 1.0, seed=2)
    full = fused_grid_cells(ids, mask, src, np.ones(300, bool), 500)
    dead = fused_grid_cells(ids, mask, src, np.zeros(300, bool), 500)
    assert 0 < full["fused_live"] <= full["range_live"]
    assert dead["fused_live"] == 0
    assert dead["range_live"] == full["range_live"]   # no frontier skip there
    assert full["total_fused"] >= full["fused_live"]
    assert full["total_unfused"] >= full["range_live"]
