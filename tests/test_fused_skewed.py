"""Differential harness for the fused kernel on skewed-degree inputs.

Hub-skewed source distributions (the paper's R22+ RMAT regime in
miniature) drive every launch shape the engine can select — the dense
early-exit grid, the host-planned ``worklist`` launch and the
device-compacted ``device_worklist`` launch — against the jnp oracle
``ref.fused_relax_reduce_ref``: min-semiring results must agree
**bit-identically**, sum semirings up to float reassociation, and each
launch's ``with_debug`` executed-cell counter must EXACTLY equal its
``fused_grid_cells`` host mirror.  Coverage: empty / sparse / full
frontiers, single-vertex tables, multi-chunk edge axes, unsorted ids
(where the device list overflows and the dense grid takes over), lane
counts Q ∈ {1, 3, 128} with masked tail lanes, and the stacked + sharded
engines; hypothesis drives randomized graphs on top when available.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.apps import bfs, sssp, pagerank
from repro.core import engine
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators, reference
from repro.kernels import fused_relax_reduce as FR
from repro.kernels.fused_relax_reduce import (
    EBLK, SBLK, fused_grid_cells, fused_relax_reduce_pallas,
    fused_relax_reduce_lanes_pallas,
)
from repro.kernels.ref import (
    fused_relax_reduce_lanes_ref, fused_relax_reduce_ref,
)
from repro.query.lanes import init_lane_values, run_stacked_lanes

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                       # pragma: no cover
    HAVE_HYPOTHESIS = False

GRID_MODES = ("dense", "worklist", "device_worklist")


def _assert_close(kind, got, want):
    if kind == "min":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def _assert_all_grid_modes(gval, gchg, src, w, mask, ids, nseg, relax,
                           kind, want, unitw=None):
    """Every launch shape agrees with the oracle, and its kernel-side
    executed-cell counter equals the host mirror exactly."""
    gchg_np = np.asarray(gchg)
    if gchg_np.ndim == 2:
        gchg_np = gchg_np.any(axis=-1)
    for mode in GRID_MODES:
        mirror = fused_grid_cells(np.asarray(ids), np.asarray(mask),
                                  np.asarray(src), gchg_np, nseg,
                                  grid_mode=mode)
        if unitw is None:
            got, dbg = fused_relax_reduce_pallas(
                gval, gchg, src, w, mask, ids, nseg, relax, kind,
                interpret=True, grid_mode=mode, with_debug=True)
        else:
            got, dbg = fused_relax_reduce_lanes_pallas(
                gval, gchg, unitw, src, w, mask, ids, nseg, relax, kind,
                interpret=True, grid_mode=mode, with_debug=True)
        _assert_close(kind, got, want)
        want_cells = (mirror["fused_live"] if mode == "dense"
                      else mirror["wl_cells"])
        assert int(dbg[0]) == want_cells, (mode, int(dbg[0]), mirror)


def _skewed_case(v, e, nseg, frontier_frac, seed, q=None):
    """Random case with a Zipf-skewed source distribution: a few hub
    sources own most edges."""
    rng = np.random.default_rng(seed)
    shape = (v,) if q is None else (v, q)
    gval = rng.uniform(0.0, 10.0, shape).astype(np.float32)
    gchg = rng.random(shape) < frontier_frac
    ranks = rng.permutation(v)[rng.integers(0, max(v // 8, 1), e)]
    src = ranks.astype(np.int32)                          # hub-skewed
    w = rng.uniform(0.1, 2.0, e).astype(np.float32)
    mask = rng.random(e) < 0.9
    ids = np.sort(rng.integers(0, nseg, e)).astype(np.int32)
    return tuple(jnp.asarray(x) for x in (gval, gchg, src, w, mask, ids))


# --------------------------------------------------------------------------
# kernel-level differential: every grid mode == ref
# --------------------------------------------------------------------------

# single-vertex tables, partial chunks, and multi-chunk edge axes over
# multi-block segment ranges
SKEW_SHAPES = [
    # (v, e, nseg)
    (1, 1, 1),
    (127, 300, 50),
    (128, 300, 50),
    (129, 300, 50),
    (257, 2 * EBLK + 13, SBLK + 5),
    (500, 3 * EBLK + 9, 2 * SBLK + 1),
    (300, 1000, 400),
]


@pytest.mark.parametrize("relax,kind", [
    ("add_w", "min"), ("add_one", "min"), ("mul_w", "sum")])
@pytest.mark.parametrize("v,e,nseg", SKEW_SHAPES)
def test_skewed_grid_modes_match_ref(relax, kind, v, e, nseg):
    gval, gchg, src, w, mask, ids = _skewed_case(v, e, nseg, 0.4,
                                                 seed=v + e + nseg)
    want = fused_relax_reduce_ref(gval, gchg, src, w, mask, ids, nseg,
                                  relax, kind)
    _assert_all_grid_modes(gval, gchg, src, w, mask, ids, nseg, relax,
                           kind, want)


@pytest.mark.parametrize("frontier_frac", [0.0, 0.05, 1.0])
def test_skewed_frontier_densities(frontier_frac):
    """Empty, sparse, and full frontiers: dead chunks execute no cell
    but never drop a live contribution."""
    gval, gchg, src, w, mask, ids = _skewed_case(400, 3 * EBLK + 9, 700,
                                                 frontier_frac, seed=5)
    want = fused_relax_reduce_ref(gval, gchg, src, w, mask, ids, 700,
                                  "add_w", "min")
    got, dbg = fused_relax_reduce_pallas(
        gval, gchg, src, w, mask, ids, 700, "add_w", "min",
        interpret=True, with_debug=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if frontier_frac == 0.0:
        assert np.all(np.asarray(got) == np.inf)
        assert int(dbg[0]) == 0                        # no live cells
    else:
        assert int(dbg[0]) > 0
    _assert_all_grid_modes(gval, gchg, src, w, mask, ids, 700, "add_w",
                           "min", want)


def test_unsorted_ids_device_worklist_falls_back_to_dense():
    """Unsorted ids spread every chunk over many segment blocks, so the
    live cells outgrow the static device list: the launch must take the
    dense grid instead of dropping cells."""
    e, nseg = 4 * EBLK, 8 * SBLK
    gval, gchg, src, w, mask, ids = _skewed_case(300, e, nseg, 1.0,
                                                 seed=11)
    ids = jnp.asarray(np.random.default_rng(1).permutation(
        np.asarray(ids)))
    mirror = fused_grid_cells(np.asarray(ids), np.asarray(mask),
                              np.asarray(src), np.asarray(gchg), nseg,
                              grid_mode="device_worklist")
    assert mirror["wl_cells"] > mirror["wl_launched"]    # list overflows
    want = fused_relax_reduce_ref(gval, gchg, src, w, mask, ids, nseg,
                                  "add_w", "min")
    got, dbg = fused_relax_reduce_pallas(
        gval, gchg, src, w, mask, ids, nseg, "add_w", "min",
        interpret=True, grid_mode="device_worklist", with_debug=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(dbg[0]) == mirror["fused_live"]


def test_device_worklist_pad_bounds_sorted_cells():
    """Destination-sorted edges keep every frontier's live cells within
    ``device_worklist_pad`` — the device list never overflows for the
    layout the engine ships."""
    rng = np.random.default_rng(3)
    for e, nseg in ((5 * EBLK + 7, 3 * SBLK), (EBLK, 40 * SBLK),
                    (9 * EBLK, 2 * SBLK + 1)):
        ids = np.sort(rng.integers(0, nseg, e)).astype(np.int32)
        src = rng.integers(0, 50, e).astype(np.int32)
        mask = np.ones(e, bool)
        m = fused_grid_cells(ids, mask, src, np.ones(50, bool), nseg,
                             grid_mode="device_worklist")
        assert m["wl_cells"] <= FR.device_worklist_pad(e, nseg)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(v=st.integers(1, 400), e=st.integers(1, 2 * EBLK + 300),
           nseg=st.integers(1, 600),
           frontier=st.sampled_from([0.0, 0.07, 0.5, 1.0]),
           seed=st.integers(0, 2**30))
    def test_skewed_differential_hypothesis(v, e, nseg, frontier, seed):
        """Randomized differential sweep: every grid mode == ref
        bit-identically for the min kind, on skewed-degree graphs."""
        gval, gchg, src, w, mask, ids = _skewed_case(v, e, nseg, frontier,
                                                     seed)
        want = fused_relax_reduce_ref(gval, gchg, src, w, mask, ids, nseg,
                                      "add_w", "min")
        _assert_all_grid_modes(gval, gchg, src, w, mask, ids, nseg,
                               "add_w", "min", want)


# --------------------------------------------------------------------------
# lane-batched differential: Q ∈ {1, 3, 128}, padded tail lanes
# --------------------------------------------------------------------------

@pytest.mark.parametrize("q", [1, 3, 128])
def test_skewed_lanes_match_ref(q):
    v, e, nseg = (40, 200, 60) if q == 128 else (260, 900, 300)
    gval, gchg, src, w, mask, ids = _skewed_case(v, e, nseg, 0.4,
                                                 seed=q, q=q)
    unitw = jnp.asarray(np.arange(q) % 2, jnp.int32)
    want = fused_relax_reduce_lanes_ref(gval, gchg, unitw, src, w, mask,
                                        ids, nseg, "add_w", "min")
    _assert_all_grid_modes(gval, gchg, src, w, mask, ids, nseg, "add_w",
                           "min", want, unitw=unitw)


def test_lane_padding_to_full_tile_bit_identical():
    """A Q=5 batch padded to a full 128-lane tile (masked tail lanes) is
    bit-identical to the unpadded jnp lanes, counts included."""
    q = 5
    gval, gchg, src, w, mask, ids = _skewed_case(150, 600, 200, 0.4,
                                                 seed=77, q=q)
    unitw = jnp.asarray([1, 0, 1, 0, 0], jnp.int32)
    want, want_counts = (
        fused_relax_reduce_lanes_ref(gval, gchg, unitw, src, w, mask, ids,
                                     200, "add_w", "min"),
        (np.asarray(mask)[:, None]
         & np.asarray(gchg)[np.asarray(src)]).sum(axis=0),
    )
    for lane_tile in (None, 128):
        got, counts = fused_relax_reduce_lanes_pallas(
            gval, gchg, unitw, src, w, mask, ids, 200, "add_w", "min",
            interpret=True, lane_tile=lane_tile, with_count=True)
        assert got.shape == (200, q)          # tail lanes sliced off
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(counts), want_counts)


def test_lane_padding_sum_semiring_close():
    """Padded tail lanes contribute the 0 identity under 'sum' too."""
    q = 5
    gval, gchg, src, w, mask, ids = _skewed_case(100, 400, 150, 0.6,
                                                 seed=9, q=q)
    unitw = jnp.zeros(q, jnp.int32)
    want = fused_relax_reduce_lanes_ref(gval, gchg, unitw, src, w, mask,
                                        ids, 150, "mul_w", "sum")
    got = fused_relax_reduce_lanes_pallas(
        gval, gchg, unitw, src, w, mask, ids, 150, "mul_w", "sum",
        interpret=True, lane_tile=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# engine-level differential: stacked + sharded
# --------------------------------------------------------------------------

def test_engine_skewed_worklist_bit_identical():
    """BFS/SSSP on a skewed graph: dense-grid and worklist fused launches
    are bit-identical to the jnp path, stats included."""
    g = generators.ba_skewed(260, m_per=4, seed=9).with_random_weights(
        seed=9)
    root = int(np.argmax(g.out_degrees()))
    cfg_j = engine.EngineConfig()
    cfg_p = engine.EngineConfig(use_pallas=True)
    cfg_w = engine.EngineConfig(use_pallas=True, grid_mode="worklist")
    for app in (bfs, sssp):
        out_j, st_j, _ = app(g, root, num_shards=8, rpvo_max=4, cfg=cfg_j)
        for cfg in (cfg_p, cfg_w):
            out, st, _ = app(g, root, num_shards=8, rpvo_max=4, cfg=cfg)
            np.testing.assert_array_equal(out, out_j)
            assert int(st.messages) == int(st_j.messages)
            assert int(st.iterations) == int(st_j.iterations)
    np.testing.assert_array_equal(
        bfs(g, root, num_shards=8, rpvo_max=4, cfg=cfg_j)[0],
        reference.bfs_levels(g, root))


@pytest.mark.parametrize("exchange", ["dense", "compact"])
def test_engine_pagerank_worklist_matches_jnp(exchange):
    """PageRank's constant frontier plans one worklist reused by every
    traced round — same ranks as the jnp path and the dense grid."""
    g = generators.rmat(8, edge_factor=6, seed=3)
    cfg_j = engine.EngineConfig(exchange=exchange)
    pr_j, _ = pagerank(g, iters=15, num_shards=8, rpvo_max=4, cfg=cfg_j)
    for grid_mode in ("dense", "worklist"):
        cfg = engine.EngineConfig(exchange=exchange, use_pallas=True,
                                  grid_mode=grid_mode)
        pr, _ = pagerank(g, iters=15, num_shards=8, rpvo_max=4, cfg=cfg)
        np.testing.assert_allclose(pr, pr_j, rtol=1e-5, atol=1e-9)


def test_engine_fused_sharded_matches_stacked():
    from jax.sharding import Mesh
    g = generators.erdos_renyi(180, avg_degree=4.0, seed=21)
    root = int(g.src[0])
    cfg = engine.EngineConfig(use_pallas=True)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    lv_st, _, _ = bfs(g, root, num_shards=1, cfg=cfg)
    lv_sh, _, _ = bfs(g, root, num_shards=1, mesh=mesh, cfg=cfg)
    np.testing.assert_array_equal(lv_sh, lv_st)
    np.testing.assert_array_equal(lv_st, reference.bfs_levels(g, root))


@pytest.mark.parametrize("exchange", ["dense", "compact"])
def test_laned_engine_skewed_matches_jnp(exchange):
    """Mixed BFS/SSSP lane batch through the serving runner: the laned
    fused kernel (dense grid and worklist) must be bit-identical to the
    laned jnp path, dense and compact exchange alike."""
    g = generators.ba_skewed(200, m_per=3, seed=4).with_random_weights(
        seed=4)
    part = build_partition(g, PartitionConfig(num_shards=4, rpvo_max=4))
    init, unitw = init_lane_values(
        part, [("bfs", 0), ("sssp", 5), ("bfs", [1, 7])])
    val_j, st_j = run_stacked_lanes(
        part, init, unitw, cfg=engine.EngineConfig(exchange=exchange))
    for grid_mode in ("dense", "worklist"):
        cfg = engine.EngineConfig(exchange=exchange, use_pallas=True,
                                  grid_mode=grid_mode)
        val, st = run_stacked_lanes(part, init, unitw, cfg=cfg)
        np.testing.assert_array_equal(np.asarray(val), np.asarray(val_j))
        np.testing.assert_array_equal(np.asarray(st.messages),
                                      np.asarray(st_j.messages))


def test_laned_engine_fused_sharded_matches_stacked():
    from jax.sharding import Mesh
    from repro.query.lanes import run_sharded_lanes
    g = generators.ba_skewed(150, m_per=3, seed=6).with_random_weights(
        seed=6)
    part = build_partition(g, PartitionConfig(num_shards=1, rpvo_max=4))
    init, unitw = init_lane_values(
        part, [("bfs", 2), ("sssp", 9), ("sssp", 0)])
    cfg = engine.EngineConfig(use_pallas=True)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    v_sh, _ = run_sharded_lanes(part, init, unitw, mesh=mesh, cfg=cfg)
    v_st, _ = run_stacked_lanes(part, init, unitw, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(v_sh), np.asarray(v_st))


def test_smem_guard_warns_on_dense_and_worklist_tables():
    """``smem_budget_bytes`` prices the scalar-prefetch tables of the
    launch actually made: 3 rows per chunk on the dense grid, 2 per
    planned cell (+ nlive) on a worklist launch."""
    gval, gchg, src, w, mask, ids = _skewed_case(100, 3 * EBLK, 300, 0.5,
                                                 seed=2)
    assert FR.smem_table_bytes(3) == 3 * 3 * 4
    assert FR.smem_table_bytes(3, wl_cells=8) == (2 * 8 + 1) * 4
    with pytest.warns(UserWarning, match="smem_budget_bytes"):
        fused_relax_reduce_pallas(gval, gchg, src, w, mask, ids, 300,
                                  "add_w", "min", interpret=True,
                                  smem_budget_bytes=8)
    with pytest.warns(UserWarning, match="smem_budget_bytes"):
        fused_relax_reduce_pallas(gval, gchg, src, w, mask, ids, 300,
                                  "add_w", "min", interpret=True,
                                  grid_mode="worklist", smem_budget_bytes=8)
