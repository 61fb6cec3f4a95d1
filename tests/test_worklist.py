"""Worklist-launch differential tests (ISSUE 5 tentpole).

The fused kernel's dense grid launches every (num_sblk, num_chunks) cell
and early-exits the dead ones; ``grid_mode='worklist'`` launches a 1-D
grid over just the live cells, dropping cells whose chunk has no
frontier-active edge in their block (the dst filter).  Every case here
drives the
worklist twins against the dense kernels and the jnp oracle — min
semirings must agree **bit-identically**, sum up to the partial
scatter's reassociation — and asserts the host-side planner mirror
(``fused_grid_cells(grid_mode='worklist')``) EXACTLY equals the
kernel-side ``with_debug`` executed-cell counter.
ISSUE 8 extends the suite with the device-compaction differential leg:
``grid_mode='device_worklist'`` builds the same live-(i, j) cell list ON
DEVICE (cumsum-scatter over the frontier chunk bitmap) — every case
asserts the device-compacted list equals the host ``plan_worklist``
output (order-normalized; exactly equal in the planner's j-major dense
order), under jit, across lane counts, and over real sharded
collectives in a subprocess.
"""
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.apps import bfs, sssp
from repro.core import actions, engine
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators, reference
from repro.kernels.fused_relax_reduce import (
    EBLK, SBLK, WL_PAD, WorklistPlanner, build_device_worklist,
    device_worklist_pad, fused_grid_cells,
    fused_relax_reduce_lanes_pallas, fused_relax_reduce_pallas,
    plan_worklist, smem_table_bytes,
)
from repro.kernels.ref import (
    fused_relax_reduce_lanes_ref, fused_relax_reduce_ref,
)
from repro.query.lanes import init_lane_values, run_stacked_lanes


def _hub_case(v, e, nseg, frontier_frac, seed, q=None):
    # sources drawn from a small permuted hub pool (max v//8 distinct
    # sources), so frontier activity clusters and the per-cell dst
    # filter has structure to bite on
    rng = np.random.default_rng(seed)
    shape = (v,) if q is None else (v, q)
    gval = rng.uniform(0.0, 10.0, shape).astype(np.float32)
    gchg = rng.random(shape) < frontier_frac
    src = rng.permutation(v)[rng.integers(0, max(v // 8, 1), e)] \
        .astype(np.int32)
    w = rng.uniform(0.1, 2.0, e).astype(np.float32)
    mask = rng.random(e) < 0.9
    ids = np.sort(rng.integers(0, nseg, e)).astype(np.int32)
    return tuple(jnp.asarray(x) for x in (gval, gchg, src, w, mask, ids))


def _wl_mirror(src, mask, ids, gchg, nseg):
    gchg = np.asarray(gchg)
    if gchg.ndim == 2:
        gchg = gchg.any(axis=-1)
    return fused_grid_cells(np.asarray(ids), np.asarray(mask),
                            np.asarray(src), gchg, nseg,
                            grid_mode="worklist")


# --------------------------------------------------------------------------
# kernel-level differential: worklist == dense == ref, mirror exact
# --------------------------------------------------------------------------

WL_SHAPES = [
    # (v, e, nseg)
    (1, 1, 1),
    (127, 300, 50),
    (129, 300, 50),
    (257, 2 * EBLK + 13, SBLK + 5),
    (500, 3 * EBLK + 9, 2 * SBLK + 1),
    (300, 1000, 400),
]


@pytest.mark.parametrize("relax,kind", [
    ("add_w", "min"), ("add_one", "min"), ("mul_w", "sum")])
@pytest.mark.parametrize("v,e,nseg", WL_SHAPES)
def test_worklist_matches_dense_and_ref(relax, kind, v, e, nseg):
    gval, gchg, src, w, mask, ids = _hub_case(v, e, nseg, 0.4,
                                              seed=v + e + nseg)
    want = fused_relax_reduce_ref(gval, gchg, src, w, mask, ids, nseg,
                                  relax, kind)
    mirror = _wl_mirror(src, mask, ids, gchg, nseg)
    wl, dbg = fused_relax_reduce_pallas(
        gval, gchg, src, w, mask, ids, nseg, relax, kind, interpret=True,
        grid_mode="worklist", with_debug=True)
    dense = fused_relax_reduce_pallas(gval, gchg, src, w, mask, ids, nseg,
                                      relax, kind, interpret=True)
    if kind == "min":
        np.testing.assert_array_equal(np.asarray(wl), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(wl), np.asarray(dense))
    else:
        np.testing.assert_allclose(np.asarray(wl), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    # the planner IS the launch: the kernel counter must mirror it
    assert int(dbg[0]) == mirror["wl_cells"]
    # dst filtering can only shrink the launch
    assert mirror["wl_cells"] <= mirror["fused_live"]


@pytest.mark.parametrize("frontier_frac", [0.0, 0.05, 1.0])
def test_worklist_frontier_densities(frontier_frac):
    gval, gchg, src, w, mask, ids = _hub_case(400, 3 * EBLK + 9, 700,
                                                 frontier_frac, seed=5)
    want = fused_relax_reduce_ref(gval, gchg, src, w, mask, ids, 700,
                                  "add_w", "min")
    got, dbg = fused_relax_reduce_pallas(
        gval, gchg, src, w, mask, ids, 700, "add_w", "min", interpret=True,
        grid_mode="worklist", with_debug=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    mirror = _wl_mirror(src, mask, ids, gchg, 700)
    if frontier_frac == 0.0:
        # an empty frontier launches only the WL_PAD dead pad cells
        assert mirror["wl_cells"] == 0
        assert mirror["wl_launched"] == WL_PAD
        assert int(dbg[0]) == 0
        assert np.all(np.asarray(got) == np.inf)
    else:
        assert int(dbg[0]) == mirror["wl_cells"] > 0


def test_worklist_padding_is_power_of_two_bucket():
    gval, gchg, src, w, mask, ids = _hub_case(500, 3 * EBLK + 9,
                                                 2 * SBLK + 1, 0.5, seed=8)
    mirror = _wl_mirror(src, mask, ids, gchg, 2 * SBLK + 1)
    launched = mirror["wl_launched"]
    assert launched >= max(mirror["wl_cells"], WL_PAD)
    assert launched & (launched - 1) == 0      # power of two
    assert launched < 2 * max(mirror["wl_cells"], WL_PAD)


def test_dst_filter_drops_cells_and_tiles():
    """One edge chunk spanning four dst blocks, frontier-active only on
    the sources feeding block 0: the dense grid runs the chunk against
    all four blocks, the dst-filtered worklist only against block 0."""
    v, nseg = 512, 4 * SBLK
    ids = np.repeat(np.arange(4, dtype=np.int32) * SBLK, 128)
    src = np.arange(512, dtype=np.int32)          # block b <- srcs b*128..
    gchg_np = np.zeros(v, bool)
    gchg_np[:128] = True                          # only block 0's sources
    rng = np.random.default_rng(0)
    gval = jnp.asarray(rng.uniform(0, 10, v).astype(np.float32))
    w = jnp.ones(512, jnp.float32)
    mask = jnp.ones(512, bool)
    mirror = _wl_mirror(src, mask, ids, gchg_np, nseg)
    assert mirror["fused_live"] == 4
    assert mirror["wl_cells"] == 1
    want = fused_relax_reduce_ref(gval, jnp.asarray(gchg_np),
                                  jnp.asarray(src), w, mask,
                                  jnp.asarray(ids), nseg, "add_w", "min")
    got, dbg = fused_relax_reduce_pallas(
        gval, jnp.asarray(gchg_np), jnp.asarray(src), w, mask,
        jnp.asarray(ids), nseg, "add_w", "min", interpret=True,
        grid_mode="worklist", with_debug=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(dbg[0]) == 1


def test_worklist_cells_are_j_major():
    """The planner emits cells chunk-major (j outer, block i inner), the
    order the device compaction reproduces."""
    gval, gchg, src, w, mask, ids = _hub_case(300, 3 * EBLK + 5,
                                              3 * SBLK, 1.0, seed=12)
    wl, info = plan_worklist(np.asarray(ids), np.asarray(mask),
                             np.asarray(src), np.asarray(gchg), 3 * SBLK,
                             dst_filter=False)
    n = info.cells
    pairs = list(zip(np.asarray(wl.wl_j)[:n].tolist(),
                     np.asarray(wl.wl_i)[:n].tolist()))
    assert n > 1 and pairs == sorted(pairs)


def test_worklist_under_tracing_requires_plan():
    import jax
    gval, gchg, src, w, mask, ids = _hub_case(64, 100, 40, 0.5, seed=2)

    @jax.jit
    def f(gval, gchg):
        return fused_relax_reduce_pallas(gval, gchg, src, w, mask, ids,
                                         40, "add_w", "min", interpret=True,
                                         grid_mode="worklist")

    with pytest.raises(ValueError, match="host-side"):
        f(gval, gchg)


# --------------------------------------------------------------------------
# laned worklist twins
# --------------------------------------------------------------------------

@pytest.mark.parametrize("q", [1, 3, 128])
def test_worklist_lanes_match_ref(q):
    v, e, nseg = (40, 200, 60) if q == 128 else (260, 900, 300)
    gval, gchg, src, w, mask, ids = _hub_case(v, e, nseg, 0.4,
                                                 seed=q, q=q)
    unitw = jnp.asarray(np.arange(q) % 2, jnp.int32)
    want = fused_relax_reduce_lanes_ref(gval, gchg, unitw, src, w, mask,
                                        ids, nseg, "add_w", "min")
    mirror = _wl_mirror(src, mask, ids, gchg, nseg)
    got, dbg = fused_relax_reduce_lanes_pallas(
        gval, gchg, unitw, src, w, mask, ids, nseg, "add_w", "min",
        interpret=True, grid_mode="worklist", with_debug=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(dbg[0]) == mirror["wl_cells"]


def test_worklist_lanes_sum_semiring_close():
    q = 5
    gval, gchg, src, w, mask, ids = _hub_case(100, 400, 150, 0.6,
                                                 seed=9, q=q)
    unitw = jnp.zeros(q, jnp.int32)
    want = fused_relax_reduce_lanes_ref(gval, gchg, unitw, src, w, mask,
                                        ids, 150, "mul_w", "sum")
    got = fused_relax_reduce_lanes_pallas(
        gval, gchg, unitw, src, w, mask, ids, 150, "mul_w", "sum",
        interpret=True, grid_mode="worklist")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# SMEM-footprint guard (ISSUE 5 satellite)
# --------------------------------------------------------------------------

def test_smem_table_bytes_shapes():
    assert smem_table_bytes(10) == 10 * 3 * 4
    assert smem_table_bytes(10, wl_cells=8) == (2 * 8 + 1) * 4
    mirror = fused_grid_cells(
        np.zeros(10, np.int64), np.ones(10, bool), np.zeros(10, np.int64),
        np.ones(16, bool), 8, grid_mode="worklist")
    assert mirror["smem_table_bytes"] == smem_table_bytes(
        1, mirror["wl_launched"])


@pytest.mark.parametrize("seed", [0, 1])
def test_planner_range_cells_equal_dense_grid(seed):
    """The planner lists each live chunk's intersecting blocks from its
    [lo, hi] id range; that must be exactly the dense grid's live-cell
    matrix, in j-major order."""
    gval, gchg, src, w, mask, ids = _hub_case(300, 4 * EBLK + 3,
                                              5 * SBLK + 7, 0.3, seed=seed)
    ids_np, mask_np = np.asarray(ids), np.asarray(mask)
    act = mask_np & np.asarray(gchg)[np.asarray(src)]
    e_pad = 5 * EBLK
    pad = lambda x, fill: np.concatenate(  # noqa: E731
        [x, np.full(e_pad - x.shape[0], fill, x.dtype)])
    idc = pad(ids_np.astype(np.int64), 0).reshape(-1, EBLK)
    mkc = pad(mask_np, False).reshape(-1, EBLK)
    acc = pad(act, False).reshape(-1, EBLK)
    lo = np.where(mkc, idc, np.iinfo(np.int64).max).min(axis=1)
    hi = np.where(mkc, idc, -1).max(axis=1)
    seg0 = np.arange(6)[:, None] * SBLK
    live = (hi >= seg0) & (lo < seg0 + SBLK) & acc.any(axis=1)[None, :]
    jj, ii = np.nonzero(live.T)
    wl, info = plan_worklist(ids_np, mask_np, np.asarray(src),
                             np.asarray(gchg), 5 * SBLK + 7,
                             dst_filter=False)
    assert info.cells == info.dense_live == jj.shape[0]
    np.testing.assert_array_equal(np.asarray(wl.wl_i)[:info.cells], ii)
    np.testing.assert_array_equal(np.asarray(wl.wl_j)[:info.cells], jj)


def test_planner_warns_when_worklist_tables_exceed_smem_budget():
    """The frontier-dependent worklist tables can only be priced at plan
    time: a planner armed with smem_budget_bytes warns once when a
    round's tables would overflow it."""
    gval, gchg, src, w, mask, ids = _hub_case(300, 2 * EBLK, 400, 1.0,
                                                 seed=4)
    planner = WorklistPlanner(np.asarray(ids), np.asarray(mask),
                              np.asarray(src), 400, smem_budget_bytes=64)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        _, info = planner.plan(np.asarray(gchg))
        planner.plan(np.asarray(gchg))           # warned once, not twice
    assert info.smem_table_bytes > 64
    assert sum("smem_budget_bytes" in str(r.message) for r in rec) == 1
    # an unarmed planner stays silent
    quiet = WorklistPlanner(np.asarray(ids), np.asarray(mask),
                            np.asarray(src), 400)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        quiet.plan(np.asarray(gchg))
    assert not rec


def test_engine_config_grid_mode_validation():
    with pytest.raises(ValueError, match="grid_mode"):
        engine.EngineConfig(grid_mode="sparse")
    with pytest.raises(ValueError, match="smem_budget_bytes"):
        engine.EngineConfig(smem_budget_bytes=0)
    assert not engine.EngineConfig(grid_mode="worklist").wants_worklist
    assert engine.EngineConfig(grid_mode="worklist",
                               use_pallas=True).wants_worklist


# --------------------------------------------------------------------------
# engine-level: host-driven worklist rounds == traced dense rounds
# --------------------------------------------------------------------------

@pytest.mark.parametrize("exchange", ["dense", "compact"])
def test_engine_worklist_matches_dense(exchange):
    g = generators.ba_skewed(260, m_per=4, seed=9).with_random_weights(
        seed=9)
    root = int(np.argmax(g.out_degrees()))
    cfg_d = engine.EngineConfig(exchange=exchange, use_pallas=True)
    cfg_w = engine.EngineConfig(exchange=exchange, use_pallas=True,
                                grid_mode="worklist")
    cfg_a = engine.EngineConfig(exchange=exchange, use_pallas=True,
                                grid_mode="auto")
    for app in (bfs, sssp):
        out_d, st_d, _ = app(g, root, num_shards=8, rpvo_max=4, cfg=cfg_d)
        for cfg in (cfg_w, cfg_a):
            out_w, st_w, _ = app(g, root, num_shards=8, rpvo_max=4,
                                 cfg=cfg)
            np.testing.assert_array_equal(out_w, out_d)
            assert int(st_w.messages) == int(st_d.messages)
            assert int(st_w.iterations) == int(st_d.iterations)
            assert int(st_w.work_actions) == int(st_d.work_actions)
    np.testing.assert_array_equal(
        bfs(g, root, num_shards=8, rpvo_max=4, cfg=cfg_w)[0],
        reference.bfs_levels(g, root))


def test_worklist_launches_track_frontier_on_ring():
    """BFS on a ring: one live vertex per round, so every round's
    worklist is a handful of cells while the dense grid stays fixed —
    the ISSUE-5 acceptance shape (4 cells vs 96)."""
    g = generators.ring(4 * EBLK)
    part = build_partition(g, PartitionConfig(num_shards=4, rpvo_max=1))
    sem = actions.BFS
    arrays = engine.DeviceArrays.from_partition(part)
    init = engine.init_values(part, sem, {0: 0.0})
    val = jnp.asarray(init)
    chg = sem.improved(val, jnp.full_like(val, sem.identity)) \
        & arrays.slot_valid
    cfg = engine.EngineConfig(use_pallas=True, grid_mode="worklist")
    planner = engine.launch_planner(part, cfg)
    total = part.S * part.R_max
    for _ in range(6):
        gchg = np.asarray(chg).reshape(-1)
        wl, info = planner.plan(gchg)
        mirror = fused_grid_cells(part.edge_dst_flat, part.edge_mask,
                                  part.edge_src_root_flat, gchg, total)
        assert info.cells <= mirror["fused_live"]
        assert info.launched <= max(2 * max(info.cells, 1), WL_PAD)
        assert info.launched < mirror["total_fused"] or \
            mirror["total_fused"] <= WL_PAD
        val, chg, _ = engine._fixpoint_round_stacked(
            sem, arrays, cfg, part.S, part.R_max, val, chg, worklist=wl)
    # deep in the ring walk the frontier is ONE vertex: a worklist of a
    # couple of cells vs the dense grid's full launch
    assert info.cells <= 4


@pytest.mark.parametrize("exchange", ["dense", "compact"])
def test_laned_engine_worklist_matches_dense(exchange):
    g = generators.ba_skewed(200, m_per=3, seed=4).with_random_weights(
        seed=4)
    part = build_partition(g, PartitionConfig(num_shards=4, rpvo_max=4))
    init, unitw = init_lane_values(
        part, [("bfs", 0), ("sssp", 5), ("bfs", [1, 7])])
    cfg_d = engine.EngineConfig(exchange=exchange, use_pallas=True)
    cfg_w = engine.EngineConfig(exchange=exchange, use_pallas=True,
                                grid_mode="worklist")
    val_d, st_d = run_stacked_lanes(part, init, unitw, cfg=cfg_d)
    val_w, st_w = run_stacked_lanes(part, init, unitw, cfg=cfg_w)
    np.testing.assert_array_equal(np.asarray(val_w), np.asarray(val_d))
    np.testing.assert_array_equal(np.asarray(st_w.messages),
                                  np.asarray(st_d.messages))
    np.testing.assert_array_equal(np.asarray(st_w.rounds),
                                  np.asarray(st_d.rounds))
    np.testing.assert_array_equal(np.asarray(st_w.work_actions),
                                  np.asarray(st_d.work_actions))


# --------------------------------------------------------------------------
# ISSUE 8: device-side frontier compaction == host planner, exactly
# --------------------------------------------------------------------------

def _device_cells(gchg, src, mask, ids, nseg):
    """Build the device worklist and return its live (i, j) list plus the
    static launch length — all leaves are traced-capable arrays."""
    wl = build_device_worklist(
        jnp.asarray(gchg).reshape(-1), jnp.asarray(src),
        jnp.asarray(mask), jnp.asarray(ids), nseg)
    n = int(wl.nlive[0])
    cells = list(zip(np.asarray(wl.wl_i)[:n].tolist(),
                     np.asarray(wl.wl_j)[:n].tolist()))
    return cells, int(np.asarray(wl.wl_i).shape[0])


def _host_cells(gchg, src, mask, ids, nseg):
    """The host oracle: ``plan_worklist`` without the dst filter (the
    device compaction keeps every live cell) — j-major dense order."""
    wl, info = plan_worklist(
        np.asarray(ids), np.asarray(mask), np.asarray(src),
        np.asarray(gchg).reshape(-1), nseg, dst_filter=False)
    n = int(wl.nlive[0])
    return list(zip(np.asarray(wl.wl_i)[:n].tolist(),
                    np.asarray(wl.wl_j)[:n].tolist())), info


@pytest.mark.parametrize("v,e,nseg", WL_SHAPES)
def test_device_compaction_equals_host_plan(v, e, nseg):
    gval, gchg, src, w, mask, ids = _hub_case(v, e, nseg, 0.4,
                                              seed=v + e + nseg)
    dev, launched = _device_cells(gchg, src, mask, ids, nseg)
    host, _ = _host_cells(gchg, src, mask, ids, nseg)
    # same j-major dense order, not merely the same set
    assert dev == host
    assert sorted(dev) == sorted(host)          # order-normalized too
    assert launched == device_worklist_pad(e, nseg)
    # the dense early-exit grid's live count is the same population
    mirror = fused_grid_cells(np.asarray(ids), np.asarray(mask),
                              np.asarray(src), np.asarray(gchg), nseg,
                              grid_mode="device_worklist")
    assert len(dev) == mirror["wl_cells"] == mirror["fused_live"]
    assert mirror["wl_launched"] == launched


@pytest.mark.parametrize("case", ["empty", "single_vertex",
                                  "tile_boundary", "skewed_hub"])
def test_device_compaction_edge_cases(case):
    v, e, nseg = 300, 2 * EBLK + 9, 2 * SBLK + 1
    rng = np.random.default_rng(17)
    src = rng.integers(0, v, e).astype(np.int32)
    mask = rng.random(e) < 0.9
    ids = np.sort(rng.integers(0, nseg, e)).astype(np.int32)
    if case == "empty":
        gchg = np.zeros(v, bool)
    elif case == "single_vertex":
        gchg = np.zeros(v, bool)
        gchg[int(src[0])] = True
    elif case == "tile_boundary":
        # live exactly at the EBLK chunk seam: edge EBLK-1 and EBLK
        gchg = np.zeros(v, bool)
        gchg[src[EBLK - 1]] = True
        gchg[src[EBLK]] = True
        mask[:] = True
    else:                                        # skewed_hub
        hub = int(np.bincount(src, minlength=v).argmax())
        gchg = np.zeros(v, bool)
        gchg[hub] = True
    dev, launched = _device_cells(gchg, src, mask, ids, nseg)
    host, _ = _host_cells(gchg, src, mask, ids, nseg)
    assert dev == host
    assert launched == device_worklist_pad(e, nseg)
    if case == "empty":
        assert dev == []
        # the static pad still launches; every cell is a masked no-op
        gval = jnp.asarray(rng.uniform(0, 10, v).astype(np.float32))
        out, dbg = fused_relax_reduce_pallas(
            gval, jnp.asarray(gchg), jnp.asarray(src),
            jnp.ones(e, jnp.float32), jnp.asarray(mask),
            jnp.asarray(ids), nseg, "add_w", "min", interpret=True,
            grid_mode="device_worklist", with_debug=True)
        assert np.all(np.asarray(out) == np.inf)
        assert int(dbg[0]) == 0


def test_device_compaction_under_jit():
    """The whole point: compaction traces — the same call fails for the
    host-planned mode (see test_worklist_under_tracing_requires_plan)."""
    gval, gchg, src, w, mask, ids = _hub_case(64, 100, 40, 0.5, seed=2)

    @jax.jit
    def f(gval, gchg):
        return fused_relax_reduce_pallas(gval, gchg, src, w, mask, ids,
                                         40, "add_w", "min", interpret=True,
                                         grid_mode="device_worklist")

    want = fused_relax_reduce_ref(gval, gchg, src, w, mask, ids, 40,
                                  "add_w", "min")
    np.testing.assert_array_equal(np.asarray(f(gval, gchg)),
                                  np.asarray(want))


@pytest.mark.parametrize("q", [1, 3, 128])
def test_device_compaction_lanes(q):
    v, e, nseg = (40, 200, 60) if q == 128 else (260, 900, 300)
    gval, gchg, src, w, mask, ids = _hub_case(v, e, nseg, 0.4,
                                              seed=q, q=q)
    unitw = jnp.asarray(np.arange(q) % 2, jnp.int32)
    want = fused_relax_reduce_lanes_ref(gval, gchg, unitw, src, w, mask,
                                        ids, nseg, "add_w", "min")
    or_chg = np.asarray(gchg).any(axis=-1)
    host, _ = _host_cells(or_chg, src, mask, ids, nseg)
    got, dbg = fused_relax_reduce_lanes_pallas(
        gval, gchg, unitw, src, w, mask, ids, nseg, "add_w", "min",
        interpret=True, grid_mode="device_worklist", with_debug=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # kernel executed exactly the host-oracle live cells
    assert int(dbg[0]) == len(host)


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(v=st.integers(2, 400),
           e=st.integers(1, 2 * EBLK + 40),
           nseg=st.integers(1, 2 * SBLK + 9),
           frontier=st.floats(0.0, 1.0),
           seed=st.integers(0, 2**31 - 1))
    def test_device_compaction_hypothesis(v, e, nseg, frontier, seed):
        """Randomized sweep: device-compacted live cells equal the host
        plan on arbitrary skew / frontier density / tile alignment."""
        gval, gchg, src, w, mask, ids = _hub_case(v, e, nseg, frontier,
                                                  seed=seed)
        dev, launched = _device_cells(gchg, src, mask, ids, nseg)
        host, _ = _host_cells(gchg, src, mask, ids, nseg)
        assert dev == host
        assert launched == device_worklist_pad(e, nseg)


SHARDED_DEVICE_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import warnings
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro.core import actions, engine
    from repro.core.partition import PartitionConfig, build_partition
    from repro.graph import generators

    assert len(jax.devices()) == 8
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))

    g = generators.ba_skewed(400, m_per=4, seed=11).with_random_weights(
        seed=11)
    part = build_partition(g, PartitionConfig(num_shards=8, rpvo_max=2))
    init = engine.init_values(part, actions.SSSP, {0: 0.0})

    base = dict(use_pallas=True, pallas_mode="fused")
    val_d, st_d = engine.run_sharded(
        actions.SSSP, part, init, mesh, ("data", "model"),
        engine.EngineConfig(grid_mode="dense", **base))
    val_dev, st_dev = engine.run_sharded(
        actions.SSSP, part, init, mesh, ("data", "model"),
        engine.EngineConfig(grid_mode="device_worklist", **base))
    # host-planned grid_mode='worklist' cannot trace under shard_map:
    # the runner must warn ONCE and route to the device compaction
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        val_w, st_w = engine.run_sharded(
            actions.SSSP, part, init, mesh, ("data", "model"),
            engine.EngineConfig(grid_mode="worklist", **base))
        engine.run_sharded(
            actions.SSSP, part, init, mesh, ("data", "model"),
            engine.EngineConfig(grid_mode="worklist", **base))
    routed = [w for w in rec if "device_worklist" in str(w.message)]
    assert len(routed) == 1, [str(w.message) for w in rec]

    np.testing.assert_array_equal(np.asarray(val_dev), np.asarray(val_d))
    np.testing.assert_array_equal(np.asarray(val_w), np.asarray(val_d))
    for f in ("iterations", "messages", "work_actions", "pruned_actions"):
        assert int(getattr(st_dev, f)) == int(getattr(st_d, f))
        assert int(getattr(st_w, f)) == int(getattr(st_d, f))
    print("SHARDED_DEVICE_WL_OK it=%d" % int(st_dev.iterations))
""")


def test_device_compaction_sharded_8dev_subprocess():
    """8-host-device sharded parity: the device-compacted worklist grid
    executes INSIDE run_sharded's traced collective loop and matches the
    dense sharded run exactly; 'worklist' warns once and routes."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = os.environ.copy()
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"    # see test_engine_sharded.py
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_DEVICE_CHILD], env=env,
        capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr}"
    assert "SHARDED_DEVICE_WL_OK" in out.stdout


def test_engine_device_worklist_matches_dense():
    """run_stacked under grid_mode='device_worklist': the whole fixpoint
    is one traced while_loop dispatch; values and stats equal dense."""
    g = generators.ba_skewed(260, m_per=4, seed=9).with_random_weights(
        seed=9)
    root = int(np.argmax(g.out_degrees()))
    cfg_d = engine.EngineConfig(use_pallas=True)
    cfg_dev = engine.EngineConfig(use_pallas=True,
                                  grid_mode="device_worklist")
    for app in (bfs, sssp):
        out_d, st_d, _ = app(g, root, num_shards=8, rpvo_max=4, cfg=cfg_d)
        out_v, st_v, _ = app(g, root, num_shards=8, rpvo_max=4,
                             cfg=cfg_dev)
        np.testing.assert_array_equal(out_v, out_d)
        for f in ("iterations", "messages", "work_actions",
                  "pruned_actions"):
            assert int(getattr(st_v, f)) == int(getattr(st_d, f))


def test_planner_live_fraction_and_auto_threshold():
    gval, gchg, src, w, mask, ids = _hub_case(300, 1000, 400, 1.0,
                                                 seed=3)
    planner = WorklistPlanner(np.asarray(ids), np.asarray(mask),
                              np.asarray(src), 400)
    dense_frac = planner.live_fraction(np.asarray(gchg))
    assert 0.0 < dense_frac <= 1.0
    assert planner.live_fraction(np.zeros(300, bool)) == 0.0
    cfg_auto = engine.EngineConfig(use_pallas=True, grid_mode="auto")
    # a dead frontier is maximally sparse -> auto must plan a worklist
    assert engine.plan_round_worklist(
        planner, cfg_auto, np.zeros(300, bool)) is not None
    if dense_frac >= engine.WORKLIST_AUTO_THRESHOLD:
        assert engine.plan_round_worklist(
            planner, cfg_auto, np.asarray(gchg)) is None


@pytest.mark.parametrize("laned", [False, True])
def test_worklist_launch_slices_match_one_launch(monkeypatch, laned):
    """A worklist longer than ``WL_LAUNCH_CELLS`` runs as several
    pallas_calls (each call's cell list must fit SMEM); the sliced launch
    equals the oracle and counts every live cell once."""
    from repro.kernels import fused_relax_reduce as FR
    q = 3 if laned else None
    gval, gchg, src, w, mask, ids = _hub_case(400, 6 * EBLK + 9, 700, 1.0,
                                              seed=21, q=q)
    mirror = _wl_mirror(src, mask, ids, gchg, 700)
    monkeypatch.setattr(FR, "WL_LAUNCH_CELLS", 4)
    assert mirror["wl_launched"] > FR.WL_LAUNCH_CELLS
    if laned:
        unitw = jnp.asarray([1, 0, 1], jnp.int32)
        want = fused_relax_reduce_lanes_ref(gval, gchg, unitw, src, w, mask,
                                            ids, 700, "add_w", "min")
        got, dbg = fused_relax_reduce_lanes_pallas(
            gval, gchg, unitw, src, w, mask, ids, 700, "add_w", "min",
            interpret=True, grid_mode="worklist", with_debug=True)
    else:
        want = fused_relax_reduce_ref(gval, gchg, src, w, mask, ids, 700,
                                      "add_w", "min")
        got, dbg = fused_relax_reduce_pallas(
            gval, gchg, src, w, mask, ids, 700, "add_w", "min",
            interpret=True, grid_mode="worklist", with_debug=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(dbg[0]) == mirror["wl_cells"]
