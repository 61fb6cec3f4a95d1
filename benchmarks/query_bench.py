"""Multi-query serving benchmark: lane batching vs a serial query loop
(ISSUE 2).

Three measurements on one shared rhizome-partitioned RMAT graph:

* **serial**  — a 16-query mixed BFS/SSSP workload run one query at a
  time through the laned runner with Q=1 (compiled once, reused), the
  per-query baseline a naive serving loop would pay;
* **batched** — the same 16 queries as 16 lanes of ONE laned fixpoint
  (one compiled round advances every live query; converged lanes ride
  along inert).  The acceptance bar: aggregate queries/s must beat the
  serial loop;
* **server**  — ``QueryServer`` continuous batching over a deeper queue
  (3x lanes): requests join lanes freed mid-flight, giving per-query
  latency percentiles and lane-occupancy, the serving analog of the
  paper's always-busy compute cells.

Also emits the per-round OR-frontier grid-cell counts for the fused
laned kernel (a grid cell executes iff its edge chunk is live in at
least one lane), and the **compact-vs-dense laned exchange volume**
(ISSUE 3): the same lane batch run on the §Perf compact targeted
exchange ships strictly fewer entries per live lane than the dense
(S, R_max, Q) inbox — ``LaneStats.exchanged`` records the per-lane
totals, and the values are asserted bit-identical.

Usage:  PYTHONPATH=src python benchmarks/query_bench.py [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time

import common  # compile cache; --seed helper
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import actions, engine
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators
from repro.kernels.fused_relax_reduce import fused_grid_cells
from repro.query import QueryServer
from repro.query.lanes import (
    _lane_round_stacked, init_lane_values, make_stacked_lanes_fn,
)


def _mixed_queries(g, n_queries, seed=0):
    rng = np.random.default_rng(seed)
    deg = np.argsort(-g.out_degrees())
    pool = deg[: max(4 * n_queries, 64)]
    roots = rng.choice(pool, size=n_queries, replace=False)
    return [("bfs" if i % 2 == 0 else "sssp", int(r))
            for i, r in enumerate(roots)]


def _timed_run(fn, init, unitw, chg, repeats):
    best = np.inf
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(init, unitw, chg)
        jax.tree.map(lambda x: x.block_until_ready(), out)
        best = min(best, time.perf_counter() - t0)
    return out, best


def bench_batch_vs_serial(part, queries, cfg, repeats=3):
    if cfg.wants_worklist:
        # host-driven laned runner: per-round worklist launches planned
        # from the OR-across-lanes frontier (ISSUE 5) — same values and
        # LaneStats as the traced fixpoint; feeds the dispatch counters
        # itself (one dispatch + host sync per round)
        from repro.query.lanes import run_stacked_lanes

        def fn(init, unitw, chg):
            return run_stacked_lanes(part, init, unitw, cfg=cfg,
                                     init_changed=chg)
    else:
        # traced whole-fixpoint runner (dense grid, or device-compacted
        # worklist under grid_mode='device_worklist'): one dispatch with
        # one result sync per call — counted through the same registry
        raw = make_stacked_lanes_fn(part, cfg)

        def fn(init, unitw, chg):
            out = raw(init, unitw, chg)
            engine._count_dispatches("bench_lanes", 1, 1)
            return out
    slot_valid = jnp.asarray(part.slot_vertex >= 0)

    def prep(qs):
        init, unitw = init_lane_values(part, qs)
        init = jnp.asarray(init)
        chg = actions.SSSP.improved(init, jnp.full_like(init, jnp.inf)) \
            & slot_valid[..., None]
        return init, jnp.asarray(unitw), chg

    # batched: all queries as lanes of one fixpoint
    init, unitw, chg = prep(queries)
    fn(init, unitw, chg)[0].block_until_ready()      # compile Q=K
    snap = common.disp_snap()
    (val_b, stats_b), wall_batch = _timed_run(fn, init, unitw, chg, 1)
    dd_b, ds_b = common.disp_delta(snap)
    if repeats > 1:
        (val_b, stats_b), wall_batch = _timed_run(fn, init, unitw, chg,
                                                  repeats)

    # serial: one compiled Q=1 runner reused across the workload
    solo = [prep([qr]) for qr in queries]
    fn(*solo[0])[0].block_until_ready()              # compile Q=1
    wall_serial = np.inf
    serial_rounds = 0
    snap = common.disp_snap()
    for rep in range(repeats):
        if rep == 1:
            dd_s, ds_s = common.disp_delta(snap)
        t0 = time.perf_counter()
        serial_rounds = 0
        for args in solo:
            _, st = fn(*args)
            serial_rounds += int(st.rounds[0])
        wall_serial = min(wall_serial, time.perf_counter() - t0)
    if repeats == 1:
        dd_s, ds_s = common.disp_delta(snap)

    k = len(queries)
    rounds_q = np.asarray(stats_b.rounds)
    rounds_b = int(rounds_q.max())
    return {
        "queries": k,
        "serial": {"wall_s": wall_serial,
                   "queries_per_s": k / wall_serial,
                   "rounds_total": serial_rounds,
                   "dispatches_total": int(dd_s),
                   "host_syncs_per_round":
                       ds_s / max(serial_rounds, 1)},
        "batched": {"wall_s": wall_batch,
                    "queries_per_s": k / wall_batch,
                    "rounds_total": rounds_b,
                    "rounds_per_query": rounds_q.tolist(),
                    "messages_per_query":
                        np.asarray(stats_b.messages).tolist(),
                    "dispatches_total": int(dd_b),
                    "host_syncs_per_round": ds_b / max(rounds_b, 1)},
        "batched_speedup": wall_serial / wall_batch,
        "batched_beats_serial": wall_batch < wall_serial,
    }


def bench_grid_cells(part, queries, cfg, max_rounds=64):
    """Round-by-round OR-frontier grid-cell counts for the laned fused
    kernel: cells live in >=1 lane vs the sum of per-lane counts a
    serial fused loop would execute."""
    arrays = engine.DeviceArrays.from_partition(part)
    init, unitw = init_lane_values(part, queries)
    val = jnp.asarray(init)
    slot_valid = jnp.asarray(part.slot_vertex >= 0)
    chg = actions.SSSP.improved(val, jnp.full_like(val, jnp.inf)) \
        & slot_valid[..., None]
    unitw = jnp.asarray(unitw)
    total = part.S * part.R_max
    rounds = []
    for _ in range(max_rounds):
        chg_h = np.asarray(chg)
        if not chg_h.any():
            break
        or_frontier = chg_h.reshape(-1, chg_h.shape[-1]).any(axis=1)
        cells_or = fused_grid_cells(
            part.edge_dst_flat, part.edge_mask, part.edge_src_root_flat,
            or_frontier, total)["fused_live"]
        cells_serial = sum(
            fused_grid_cells(
                part.edge_dst_flat, part.edge_mask,
                part.edge_src_root_flat,
                chg_h.reshape(-1, chg_h.shape[-1])[:, q], total)
            ["fused_live"]
            for q in range(chg_h.shape[-1])
            if chg_h[..., q].any())
        rounds.append({"grid_cells_or_batched": cells_or,
                       "grid_cells_serial_sum": cells_serial,
                       "live_lanes":
                           int(chg_h.reshape(-1, chg_h.shape[-1])
                               .any(axis=0).sum())})
        val, chg, _ = _lane_round_stacked(
            actions.SSSP, arrays, cfg, part.S, part.R_max, unitw, val, chg)
    return {
        "per_round": rounds,
        "grid_cells_or_total": sum(r["grid_cells_or_batched"]
                                   for r in rounds),
        "grid_cells_serial_total": sum(r["grid_cells_serial_sum"]
                                       for r in rounds),
    }


def bench_exchange_volume(part, queries, use_pallas=False):
    """Compact targeted vs dense laned exchange on one lane batch: per-
    lane exchanged-entry totals (entries shipped through the inter-shard
    exchange while the lane was live), bit-identity of the results, and
    the volume-reduction ratio — the paper's §Perf message reduction
    measured on the lane axis."""
    slot_valid = jnp.asarray(part.slot_vertex >= 0)
    init_np, unitw_np = init_lane_values(part, queries)
    init = jnp.asarray(init_np)
    chg = actions.SSSP.improved(init, jnp.full_like(init, jnp.inf)) \
        & slot_valid[..., None]
    unitw = jnp.asarray(unitw_np)
    out, vals = {}, {}
    for label, cfg in (
            ("dense", engine.EngineConfig(use_pallas=use_pallas)),
            ("compact", engine.EngineConfig(use_pallas=use_pallas,
                                            exchange="compact"))):
        fn = make_stacked_lanes_fn(part, cfg)
        val, stats = fn(init, unitw, chg)
        val.block_until_ready()
        t0 = time.perf_counter()
        val, stats = fn(init, unitw, chg)
        val.block_until_ready()
        wall = time.perf_counter() - t0
        vals[label] = np.asarray(val)
        ex = np.asarray(stats.exchanged)
        out[label] = {
            "wall_s": wall,
            "exchanged_total": int(ex.sum()),
            "exchanged_per_lane": ex.tolist(),
            "messages_total": int(np.asarray(stats.messages).sum()),
        }
    identical = bool(np.array_equal(vals["dense"], vals["compact"]))
    assert identical, "compact laned exchange diverged from dense"
    out["values_bit_identical"] = identical
    out["volume_ratio_dense_over_compact"] = (
        out["dense"]["exchanged_total"]
        / max(out["compact"]["exchanged_total"], 1))
    out["partition"] = {"R_max": part.R_max, "P_t": part.P_t,
                        "shards": part.S}
    return out


def bench_server(part, queries, n_lanes, cfg, tick_rounds=1):
    srv = QueryServer(part, n_lanes=n_lanes, ppr_lanes=0, cfg=cfg,
                      tick_rounds=tick_rounds)
    snap = common.disp_snap()
    t0 = time.perf_counter()
    for kind, root in queries:
        srv.submit(kind, root)
    results = srv.run()
    wall = time.perf_counter() - t0
    dd, ds = common.disp_delta(snap)
    lat = np.array([r.latency_s for r in results.values()])
    rounds = np.array([r.rounds for r in results.values()])
    return {
        "queries": len(queries),
        "lanes": n_lanes,
        "tick_rounds": tick_rounds,
        "wall_s": wall,
        "queries_per_s": len(queries) / wall,
        "ticks": srv.tick,
        "rounds_driven": srv.rounds_driven,
        "dispatches_total": int(dd),
        "host_syncs_per_round": ds / max(srv.rounds_driven, 1),
        "lane_occupancy": srv.occupancy(),
        "latency_s": {
            "p50": float(np.percentile(lat, 50)),
            "p90": float(np.percentile(lat, 90)),
            "p99": float(np.percentile(lat, 99)),
            "max": float(lat.max()),
        },
        "rounds_per_query": {
            "p50": float(np.percentile(rounds, 50)),
            "max": int(rounds.max()),
        },
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_query.json")
    ap.add_argument("--scale", type=int, default=10,
                    help="RMAT scale (n = 2**scale)")
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--rpvo-max", type=int, default=4)
    ap.add_argument("--lanes", type=int, default=16)
    ap.add_argument("--server-queue", type=int, default=48)
    ap.add_argument("--tick-rounds", type=int, default=4,
                    help="K-round window for the windowed-server row "
                         "(one dispatch advances every live lane K "
                         "rounds)")
    common.add_seed_arg(ap)
    common.add_obs_out_arg(ap)
    common.add_grid_mode_arg(ap)
    args = ap.parse_args()

    g = generators.rmat(args.scale, edge_factor=args.edge_factor,
                        seed=args.seed).with_random_weights(seed=args.seed)
    part = build_partition(
        g, PartitionConfig(num_shards=args.shards, rpvo_max=args.rpvo_max))
    workload = _mixed_queries(g, args.lanes, seed=args.seed + 1)
    deep_queue = _mixed_queries(g, args.server_queue, seed=args.seed + 2)

    report = {
        "bench": "query_serving",
        "graph": {"kind": "rmat", "scale": args.scale,
                  "edge_factor": args.edge_factor, "n": g.n,
                  "num_edges": g.num_edges, "seed": args.seed},
        "config": {"shards": args.shards, "rpvo_max": args.rpvo_max,
                   "lanes": args.lanes, "grid_mode": args.grid_mode,
                   "backend": jax.default_backend(),
                   "interpret_mode": jax.default_backend() != "tpu"},
        "notes": (
            "serial = one query at a time through the same compiled Q=1 "
            "laned runner; batched = the workload as lanes of one "
            "fixpoint. Grid-cell counts mirror the laned fused kernel's "
            "OR-frontier chunk skip vs the sum a serial fused loop "
            "executes. The fused variant is reported under CPU interpret "
            "mode, where kernel Python overhead dominates; the batching "
            "ratio is the portable signal. dispatches_total / "
            "host_syncs_per_round are obs-registry deltas: fused_dev "
            "(grid_mode='device_worklist') runs the whole laned fixpoint "
            "as ONE traced dispatch; server_windowed ticks in "
            "tick_rounds-round windows — one dispatch per window instead "
            "of one per round."),
        "variants": {},
    }

    variants = [("jnp", engine.EngineConfig()),
                ("fused", engine.EngineConfig(use_pallas=True))]
    if args.grid_mode != "dense":
        host_mode = args.grid_mode \
            if args.grid_mode in ("worklist", "auto") else "worklist"
        variants += [
            ("fused_wl", engine.EngineConfig(use_pallas=True,
                                             grid_mode=host_mode)),
            # on-device frontier compaction: the whole laned fixpoint is
            # ONE traced dispatch (ISSUE 8) — compare dispatches_total
            # against fused_wl's one-per-round
            ("fused_dev",
             engine.EngineConfig(use_pallas=True,
                                 grid_mode="device_worklist")),
        ]
    for label, cfg in variants:
        entry = bench_batch_vs_serial(part, workload, cfg,
                                      repeats=3 if label == "jnp" else 1)
        print(f"{label:6s} serial={entry['serial']['wall_s']:.3f}s "
              f"batched={entry['batched']['wall_s']:.3f}s "
              f"speedup={entry['batched_speedup']:.2f}x "
              f"({entry['batched']['queries_per_s']:.1f} q/s)")
        report["variants"][label] = entry

    report["grid_cells"] = bench_grid_cells(
        part, workload, engine.EngineConfig(use_pallas=True))
    gc = report["grid_cells"]
    print(f"grid cells: batched-OR={gc['grid_cells_or_total']} "
          f"serial-sum={gc['grid_cells_serial_total']}")

    report["exchange_volume"] = bench_exchange_volume(part, workload)
    ev = report["exchange_volume"]
    print(f"laned exchange volume: dense={ev['dense']['exchanged_total']} "
          f"compact={ev['compact']['exchanged_total']} "
          f"({ev['volume_ratio_dense_over_compact']:.2f}x reduction, "
          f"bit-identical={ev['values_bit_identical']})")

    report["server"] = bench_server(part, deep_queue, args.lanes,
                                    engine.EngineConfig())
    sv = report["server"]
    print(f"server {sv['queries']} queries / {sv['lanes']} lanes: "
          f"{sv['queries_per_s']:.1f} q/s occupancy={sv['lane_occupancy']:.2f} "
          f"p50={sv['latency_s']['p50']*1e3:.1f}ms "
          f"p99={sv['latency_s']['p99']*1e3:.1f}ms")

    # K-round window ticks (ISSUE 8): one dispatch advances every live
    # lane tick_rounds rounds — same results, ~1/K the host syncs
    report["server_windowed"] = bench_server(
        part, deep_queue, args.lanes, engine.EngineConfig(),
        tick_rounds=args.tick_rounds)
    sw = report["server_windowed"]
    print(f"server tick_rounds={sw['tick_rounds']}: "
          f"{sw['queries_per_s']:.1f} q/s ticks={sw['ticks']} "
          f"(vs {sv['ticks']}) dispatches={sw['dispatches_total']} "
          f"syncs/round={sw['host_syncs_per_round']:.2f}")

    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2)
    print(f"wrote {args.out}")
    common.finish_report(report, obs_out=args.obs_out)


if __name__ == "__main__":
    main()
