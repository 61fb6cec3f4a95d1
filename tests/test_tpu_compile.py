"""Compile rehearsals for a v5e chip, kept as tests.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology lets every Pallas launcher the engine's main path can
select compile through Mosaic here, at the shapes ``chip_smoke.py``
launches at RMAT scale 20, so a kernel the chip would refuse fails CI
instead of a chip run.  Nothing runs: these tests say nothing about
results or times.  Each asserts that the compiled program holds the
kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture (never at
import, so every pytest-xdist worker collects the same tests) and the
tests skip when it cannot be described.  ``kernels.ops._interpret`` is
steered per test: code that asks ``jax.default_backend()`` still sees the
CPU here.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import actions, engine
from repro.kernels import ops
from repro.kernels.fused_relax_reduce import (
    Worklist, fused_relax_reduce_lanes_pallas, fused_relax_reduce_pallas,
)
from repro.kernels.rhizome_segment_reduce import segment_combine_pallas

# the scale-20 smoke: 16 stacked shards on one chip ...
SLOTS, EDGES, LANES = 1_056_464, 16_767_721, 16
# the largest power-of-two worklist bucket a scale-20 round reaches
# (about n_chunks + 16 * n_blocks live cells)
WL_CELLS = 131_072
# ... and one shard per device of the four-chip phase
SHARD_SLOTS, SHARD_EDGES = 270_336, 4_210_688


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                         # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Compile, don't interpret; and keep these compiles out of the
    persistent cache (they cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _edges(sharding, e):
    def s(dt):
        return jax.ShapeDtypeStruct((e,), dt, sharding=sharding)
    return [s(jnp.int32), s(jnp.float32), s(jnp.bool_), s(jnp.int32)]


@pytest.mark.parametrize("relax,kind", [("add_w", "min"), ("mul_w", "sum")])
@pytest.mark.parametrize("grid_mode", ["dense", "worklist",
                                       "device_worklist"])
@pytest.mark.parametrize("laned", [False, True], ids=["single", "lanes"])
def test_fused_launcher_compiles(one_chip, mosaic, laned, grid_mode, relax,
                                 kind):
    # device_worklist runs in the four-chip phase's traced loops: one
    # shard's edges per device; the host-planned modes at the stacked
    # one-chip shapes
    v, e = ((SHARD_SLOTS, SHARD_EDGES) if grid_mode == "device_worklist"
            else (SLOTS, EDGES))
    shape = (v, LANES) if laned else (v,)
    args = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct(shape, jnp.bool_, sharding=one_chip)]
    if laned:
        args.append(jax.ShapeDtypeStruct((LANES,), jnp.int32,
                                         sharding=one_chip))
    args += _edges(one_chip, e)
    if grid_mode == "worklist":
        args += [jax.ShapeDtypeStruct((WL_CELLS,), jnp.int32,
                                      sharding=one_chip)] * 2
        args.append(jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip))
    launch = (fused_relax_reduce_lanes_pallas if laned
              else fused_relax_reduce_pallas)
    n_tables = len(args) - (3 if grid_mode == "worklist" else 0)

    def fn(*a):
        wl = Worklist(*a[n_tables:]) if grid_mode == "worklist" else None
        return launch(*a[:n_tables], v, relax, kind, interpret=False,
                      with_count=True, worklist=wl,
                      grid_mode="dense" if wl is not None else grid_mode)

    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("laned", [False, True], ids=["single", "lanes"])
@pytest.mark.parametrize("worklist", [False, True], ids=["dense", "wl"])
def test_kernels_carry_stable_names(one_chip, laned, worklist):
    """Each pallas_call is named, so a chip profile shows the kernel by
    that name: ``fused_relax_reduce`` (``_wl`` for the worklist grid,
    ``_lanes`` for laned messages) and ``segment_combine``.  Lowered
    only, at small shapes."""
    v, e, q, cells = 4096, 8192, 8, 64
    shape = (v, q) if laned else (v,)

    def s(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    args = [s(shape, jnp.float32), s(shape, jnp.bool_)]
    if laned:
        args.append(s((q,), jnp.int32))
    args += _edges(one_chip, e)
    n_tables = len(args)
    if worklist:
        args += [s((cells,), jnp.int32)] * 2 + [s((1,), jnp.int32)]
    launch = (fused_relax_reduce_lanes_pallas if laned
              else fused_relax_reduce_pallas)

    def fn(*a):
        wl = Worklist(*a[n_tables:]) if worklist else None
        return launch(*a[:n_tables], v, "add_w", "min", interpret=False,
                      with_count=True, worklist=wl)

    name = ("fused_relax_reduce" + ("_wl" if worklist else "")
            + ("_lanes" if laned else ""))
    text = jax.jit(fn).lower(*args).as_text()
    assert f'kernel_name = "{name}"' in text
    if not worklist and not laned:
        text = jax.jit(
            lambda d, i: segment_combine_pallas(d, i, v, "min",
                                                interpret=False)
        ).lower(s((e,), jnp.float32), s((e,), jnp.int32)).as_text()
        assert 'kernel_name = "segment_combine"' in text


@pytest.mark.parametrize("kind", ["min", "sum"])
def test_segment_combine_compiles(one_chip, mosaic, kind):
    """The unfused reduce kernel (``pallas_mode='reduce'``)."""
    text = _compiled_text(
        lambda d, i: segment_combine_pallas(d, i, SLOTS, kind,
                                            interpret=False),
        jax.ShapeDtypeStruct((EDGES,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((EDGES,), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in text


def test_sharded_fixpoint_compiles_on_4_devices(topo, mosaic):
    """The BFS fixpoint under shard_map over a 2x2 mesh with the fused
    kernel — the four-chip phase's program, one shard per device."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    S, R_max, E_max, K = 4, SHARD_SLOTS, SHARD_EDGES, 4
    P_t, R_rz = R_max, 8192
    cfg = engine.EngineConfig(use_pallas=True, grid_mode="device_worklist")
    fn, sharding = engine.make_sharded_fn(actions.BFS, S, R_max, mesh,
                                          ("data", "model"), cfg)
    assert sharding == NamedSharding(mesh, P(("data", "model")))

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    arrays = engine.DeviceArrays(
        edge_src_root_flat=s((S, E_max), jnp.int32),
        edge_dst_flat=s((S, E_max), jnp.int32),
        edge_w=s((S, E_max), jnp.float32),
        edge_mask=s((S, E_max), jnp.bool_),
        sibling_flat=s((S, R_max, K), jnp.int32),
        sibling_mask=s((S, R_max, K), jnp.bool_),
        slot_valid=s((S, R_max), jnp.bool_),
        edge_dst_compact=s((S, E_max), jnp.int32),
        inbox_slot_map=s((S, S, P_t), jnp.int32),
        rz_local=s((S, R_rz), jnp.int32),
        rz_sibling_idx=s((S, R_rz, K), jnp.int32),
        rz_sibling_mask=s((S, R_rz, K), jnp.bool_))
    compiled = fn.lower(arrays, s((S, R_max), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _stacked_arrays(sharding, S=16, R_max=66_029, E_max=1_048_000, K=4):
    """Scale-20 smoke partition shapes (16 shards stacked)."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return engine.DeviceArrays(
        edge_src_root_flat=s((S, E_max), jnp.int32),
        edge_dst_flat=s((S, E_max), jnp.int32),
        edge_w=s((S, E_max), jnp.float32),
        edge_mask=s((S, E_max), jnp.bool_),
        sibling_flat=s((S, R_max, K), jnp.int32),
        sibling_mask=s((S, R_max, K), jnp.bool_),
        slot_valid=s((S, R_max), jnp.bool_),
        edge_dst_compact=s((S, E_max), jnp.int32),
        inbox_slot_map=s((S, S, 20_000), jnp.int32),
        rz_local=s((S, 8192), jnp.int32),
        rz_sibling_idx=s((S, 8192, K), jnp.int32),
        rz_sibling_mask=s((S, 8192, K), jnp.bool_)), S, R_max


@pytest.mark.parametrize("lanes", [0, LANES], ids=["bfs", "server"])
def test_stacked_round_compiles(one_chip, mosaic, lanes):
    """One whole host-planned fixpoint round (relax, exchange, rhizome
    collapse) at the one-chip smoke's shapes and largest worklist: the
    program the BFS / SSSP loop and the server's min pool dispatch."""
    from repro import exchange
    arrays, S, R_max = _stacked_arrays(one_chip)
    cfg = engine.EngineConfig(use_pallas=True, grid_mode="worklist")
    shape = (S, R_max, lanes) if lanes else (S, R_max)

    def s(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    def fn(ar, val, chg, unitw, wl_i, wl_j, nlive):
        return exchange.fixpoint_round_stacked(
            actions.SSSP, ar, cfg, S, R_max, val, chg,
            lane_unitw=unitw if lanes else None,
            worklist=Worklist(wl_i, wl_j, nlive))

    text = _compiled_text(fn, arrays, s(shape, jnp.float32),
                          s(shape, jnp.bool_), s((max(lanes, 1),), jnp.int32),
                          s((WL_CELLS,), jnp.int32), s((WL_CELLS,), jnp.int32),
                          s((1,), jnp.int32))
    assert "tpu_custom_call" in text


def test_delta_pagerank_round_compiles(one_chip, mosaic):
    """The host loop's delta-PageRank round program
    (``engine._round_program``: tables, damping, tolerance, residuals and
    worklist all arguments) at the one-chip smoke's shapes and largest
    worklist."""
    from repro import exchange
    arrays, S, R_max = _stacked_arrays(one_chip)
    cfg = engine.EngineConfig(use_pallas=True, grid_mode="worklist")

    def s(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    def fn(ar, damping, tol, rank, delta, wl_i, wl_j, nlive):
        return engine._round_program(
            "pagerank_delta", exchange.delta_pagerank_round_stacked,
            actions.PAGERANK, cfg, ar, damping, tol, rank, delta,
            worklist=Worklist(wl_i, wl_j, nlive))

    text = _compiled_text(fn, arrays, s((), jnp.float32), s((), jnp.float32),
                          s((S, R_max), jnp.float32),
                          s((S, R_max), jnp.float32),
                          s((WL_CELLS,), jnp.int32), s((WL_CELLS,), jnp.int32),
                          s((1,), jnp.int32))
    assert "tpu_custom_call" in text
