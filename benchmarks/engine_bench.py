"""Engine hot-path benchmark: fused vs unfused relax phase (ISSUE 1)
and the sparsity-proportional worklist launches + delta-PageRank
(ISSUE 5).

Runs BFS / SSSP / PageRank / delta-PageRank on a skewed RMAT graph
through the stacked engine:

* ``fused``    — the frontier-aware relax+reduce Pallas kernel, dense
  (num_sblk, num_chunks) grid with per-cell early exit;
* ``worklist`` — the 1-D live-cell worklist launch (host-planned each
  round from the frontier): late sparse rounds launch a handful of
  padded cells instead of the full grid;
* ``unfused``  — the pre-fusion composition (``pallas_mode='reduce'``);
* ``jnp``      — no Pallas at all, the oracle.

Every worklist round ALSO launches the kernel once with ``with_debug``
and asserts the kernel-side executed-cell counter equals the host
planner mirror EXACTLY — the provably-exact accounting bar.

``pagerank_delta`` runs the push-based residual rounds at the same
round count as dense PageRank: the frontier shrinks as residuals decay,
so messages and grid cells drop round over round — the
first time the frontier machinery fires for the sum semiring.

Emits ``BENCH_engine.json`` (rounds, wall/round, messages/s, exact grid
cells, worklist-vs-dense columns) for the perf
trajectory.

Usage:  PYTHONPATH=src python benchmarks/engine_bench.py [--out PATH]
        [--seed N] [--grid-mode dense|worklist|auto]
"""
from __future__ import annotations

import argparse
import json
import time

import common  # --seed / --obs-out helpers, compile cache
import jax
import jax.numpy as jnp
import numpy as np

from common import disp_delta, disp_snap
from repro.core import actions, engine
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators
from repro.kernels import ops as kops
from repro.kernels.fused_relax_reduce import (
    fused_grid_cells, fused_relax_reduce_pallas,
)


def _debug_check(part, sem, gval, gchg, total, worklist, cells):
    """Launch the fused kernel once more with ``with_debug`` on the exact
    per-round inputs and assert the kernel-side executed-cell counter
    equals the host mirror — exercised by the CI smoke leg."""
    args = (gval, jnp.asarray(gchg),
            jnp.asarray(part.edge_src_root_flat.reshape(-1)),
            jnp.asarray(part.edge_w.reshape(-1), jnp.float32),
            jnp.asarray(part.edge_mask.reshape(-1)),
            jnp.asarray(part.edge_dst_flat.reshape(-1)))
    if worklist is not None:
        _, dbg = fused_relax_reduce_pallas(
            *args, total, sem.relax_kind, sem.segment, worklist=worklist,
            interpret=kops._interpret(), with_debug=True)
        assert int(dbg[0]) == cells["wl_cells"], (int(dbg[0]), cells)
    else:
        _, dbg = fused_relax_reduce_pallas(
            *args, total, sem.relax_kind, sem.segment,
            interpret=kops._interpret(), with_debug=True)
        assert int(dbg[0]) == cells["fused_live"], (int(dbg[0]), cells)


def bench_rounds(sem, part, sources, cfg, max_rounds, fixed_rounds=None,
                 repeats=5, damping=0.85, delta_tol=None,
                 check_debug=False):
    """Drive the stacked engine round-by-round (jitted round fn — the
    exact round the shipped runners execute), timing each round
    (best-of-``repeats``, the round fn is pure), mirroring the grid-cell
    counts from the frontier, and — for worklist variants —
    planning each round's live-cell launch exactly as the host-driven
    runners do."""
    arrays = engine.DeviceArrays.from_partition(part)
    S, R_max = part.S, part.R_max
    total = S * R_max
    planner = engine.launch_planner(part, cfg) if cfg.wants_worklist \
        else None

    if delta_tol is not None:      # delta-PageRank residual rounds
        tol_j = jnp.asarray(delta_tol, jnp.float32)
        base = (1.0 - damping) / part.n

        @jax.jit
        def round_fn(state, wl):
            rank, delta = state
            nr, nd, _, mc = engine.exchange.delta_pagerank_round_stacked(
                sem, arrays, cfg, S, R_max, damping, tol_j, rank, delta,
                worklist=wl)
            return (nr, nd), mc

        init = jnp.where(arrays.slot_valid, base, 0.0)
        state = (init, init)

        def frontier(state):
            return np.asarray((state[1] > tol_j) & arrays.slot_valid)

        def relax_inputs(state):
            return state[1].reshape(-1), frontier(state).reshape(-1)

    elif sem.segment == "sum":     # PageRank: the counted dense round
        base = (1.0 - damping) / part.n
        chg = arrays.slot_valid    # PR predicate is #t — always diffuse

        @jax.jit
        def round_fn(state, wl):
            nv, mc = engine._pagerank_round_stacked(
                sem, arrays, cfg, S, R_max, base, damping, state[0], chg,
                worklist=wl)
            return (nv,), mc

        state = (jnp.where(arrays.slot_valid, 1.0 / part.n, 0.0),)

        def frontier(_):
            return np.asarray(arrays.slot_valid)

        def relax_inputs(state):
            return state[0].reshape(-1), \
                np.asarray(arrays.slot_valid).reshape(-1)

    else:                          # BFS/SSSP: the fixpoint round

        @jax.jit
        def round_fn(state, wl):
            nv, nc, mc = engine._fixpoint_round_stacked(
                sem, arrays, cfg, S, R_max, state[0], state[1],
                worklist=wl)
            return (nv, nc), mc

        init = engine.init_values(part, sem, sources)
        val = jnp.asarray(init)
        chg = sem.improved(val, jnp.full_like(val, sem.identity)) \
            & arrays.slot_valid
        state = (val, chg)

        def frontier(state):
            return np.asarray(state[1])

        def relax_inputs(state):
            return state[0].reshape(-1), np.asarray(state[1]).reshape(-1)

    # compile outside timing (the worklist retraces per pow2 bucket; the
    # best-of-repeats timing below absorbs those)
    wl0 = (engine.plan_round_worklist(planner, cfg,
                                      frontier(state).reshape(-1))
           if planner else None)
    jax.tree.map(lambda x: x.block_until_ready(),
                 round_fn(state, wl0)[0])

    rounds = []
    n = fixed_rounds if fixed_rounds is not None else max_rounds
    for _ in range(n):
        chg_h = frontier(state)
        if fixed_rounds is None and not chg_h.any():
            break
        gchg = chg_h.reshape(-1)
        cells = fused_grid_cells(
            part.edge_dst_flat, part.edge_mask, part.edge_src_root_flat,
            gchg, total, grid_mode="worklist" if planner else "dense")
        wl = (engine.plan_round_worklist(planner, cfg, gchg)
              if planner else None)
        if check_debug and cfg.use_pallas and cfg.pallas_mode == "fused" \
                and cfg.exchange == "dense":
            gval_f, gchg_f = relax_inputs(state)
            _debug_check(part, sem, gval_f, gchg_f, total, wl, cells)
        dt = np.inf
        for _ in range(repeats):
            t0 = time.perf_counter()
            nstate, msg_count = round_fn(state, wl)
            nstate[0].block_until_ready()
            dt = min(dt, time.perf_counter() - t0)
        state = nstate
        # one *logical* dispatch + host sync per host-driven round (the
        # timing repeats re-execute the same round and are not counted)
        engine._count_dispatches("bench", 1, 1)
        row = {
            "wall_s": dt,
            "messages": int(msg_count),
            "grid_fused_live": cells["fused_live"],
            "grid_range_live": cells["range_live"],
            "grid_total_fused": cells["total_fused"],
            "grid_total_unfused": cells["total_unfused"],
        }
        if planner is not None:
            row["grid_wl_cells"] = cells["wl_cells"]
            row["grid_wl_launched"] = cells["wl_launched"]
        rounds.append(row)
    return rounds


def summarize(rounds, cell_key):
    total_msgs = sum(r["messages"] for r in rounds)
    total_wall = sum(r["wall_s"] for r in rounds)
    executed = (sum(r[cell_key] for r in rounds)
                if cell_key is not None else 0)
    out = {
        "rounds": len(rounds),
        "wall_s_total": total_wall,
        "wall_s_per_round": total_wall / max(len(rounds), 1),
        "messages_total": total_msgs,
        "messages_per_s": total_msgs / max(total_wall, 1e-12),
        "grid_cells_executed": executed,
        "per_round": rounds,
    }
    if rounds and "grid_wl_cells" in rounds[0]:
        out["wl_cells_total"] = sum(r["grid_wl_cells"] for r in rounds)
        out["wl_launched_total"] = sum(r["grid_wl_launched"]
                                      for r in rounds)
    return out


def _device_debug_check(part, sem, gval, gchg, total):
    """Launch the fused kernel once in ``grid_mode='device_worklist'``
    with ``with_debug`` and assert the kernel-side executed-cell counter
    equals the host mirror for the device-compacted launch —
    the CI device-worklist smoke leg's assertion."""
    cells = fused_grid_cells(
        part.edge_dst_flat, part.edge_mask, part.edge_src_root_flat,
        gchg, total, grid_mode="device_worklist")
    _, dbg = fused_relax_reduce_pallas(
        jnp.asarray(gval), jnp.asarray(gchg),
        jnp.asarray(part.edge_src_root_flat.reshape(-1)),
        jnp.asarray(part.edge_w.reshape(-1), jnp.float32),
        jnp.asarray(part.edge_mask.reshape(-1)),
        jnp.asarray(part.edge_dst_flat.reshape(-1)),
        total, sem.relax_kind, sem.segment,
        grid_mode="device_worklist", interpret=kops._interpret(),
        with_debug=True)
    assert int(dbg[0]) == cells["wl_cells"], (int(dbg[0]), cells)


def bench_device_fixpoint(name, sem, part, sources, max_rounds,
                          damping=0.85, delta_tol=None):
    """Run the WHOLE fixpoint through the shipped device-resident runner
    (``grid_mode='device_worklist'``, no recorder → one traced
    ``lax.while_loop``) and report wall time plus the obs-registry
    dispatch counters — the ISSUE-8 acceptance row: ``dispatches_total``
    must be exactly 1 for the full fixpoint."""
    cfg = engine.EngineConfig(use_pallas=True,
                              grid_mode="device_worklist")
    if delta_tol is not None:
        def run():
            return engine.run_pagerank_delta(
                part, damping=damping, tol=delta_tol, cfg=cfg,
                max_rounds=max_rounds)
    else:
        init = engine.init_values(part, sem, sources)

        def run():
            return engine.run_stacked(sem, part, init, cfg)

    run()                               # compile outside timing
    snap = disp_snap()
    t0 = time.perf_counter()
    val, stats = run()
    jax.block_until_ready(val)
    wall = time.perf_counter() - t0
    dd, ds = disp_delta(snap)
    assert dd == 1, f"{name}: device fixpoint took {dd} dispatches"
    rounds = int(stats.iterations)
    planner = engine.launch_planner(
        part, engine.EngineConfig(use_pallas=True, grid_mode="worklist"))
    l_pad = engine._device_l_pad(planner)
    return {
        "rounds": rounds,
        "wall_s_total": wall,
        "wall_s_per_round": wall / max(rounds, 1),
        "messages_total": int(stats.messages),
        "messages_per_s": int(stats.messages) / max(wall, 1e-12),
        "grid_cells_executed": 0,   # on device; exactness asserted in
                                    # tests/test_worklist.py vs planner
        "wl_launched_total": l_pad * rounds,
        "dispatches_total": int(dd),
        "host_syncs_per_round": ds / max(rounds, 1),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_engine.json")
    ap.add_argument("--scale", type=int, default=10,
                    help="RMAT scale (n = 2**scale)")
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--rpvo-max", type=int, default=4)
    ap.add_argument("--pr-iters", type=int, default=10)
    ap.add_argument("--pr-tol", type=float, default=3e-5,
                    help="delta-PageRank residual tolerance (default "
                         "chosen so the BENCH RMAT frontier decays "
                         "through it within --pr-iters rounds)")
    ap.add_argument("--max-rounds", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=5)
    common.add_seed_arg(ap)
    common.add_obs_out_arg(ap)
    common.add_grid_mode_arg(ap)
    args = ap.parse_args()

    g = generators.rmat(args.scale, edge_factor=args.edge_factor,
                        seed=args.seed)
    gw = g.with_random_weights(seed=args.seed)
    root = int(np.argmax(g.out_degrees()))
    pcfg = PartitionConfig(num_shards=args.shards, rpvo_max=args.rpvo_max)

    report = {
        "bench": "engine_round",
        "graph": {"kind": "rmat", "scale": args.scale,
                  "edge_factor": args.edge_factor, "n": g.n,
                  "num_edges": g.num_edges, "root": root,
                  "seed": args.seed},
        "config": {"shards": args.shards, "rpvo_max": args.rpvo_max,
                   "grid_mode": args.grid_mode, "pr_tol": args.pr_tol,
                   "backend": jax.default_backend(),
                   "interpret_mode": jax.default_backend() != "tpu"},
        "notes": (
            "Grid-cell counts are exact mirrors of each variant's launch "
            "shape (fused: dense flattened launch with frontier chunk "
            "skip; worklist: host-planned 1-D live-cell launch, kernel "
            "with_debug counters asserted equal to the mirror every "
            "round; unfused: S per-shard reduce launches, range skip "
            "only). Dense PageRank diffuses every round (predicate #t), "
            "so only the delta-PageRank rounds shrink the sum-semiring "
            "frontier — compare the pagerank_delta rows' messages/cells "
            "against pagerank at the same round count. dispatches_total / "
            "host_syncs_per_round are obs-registry deltas: host-driven "
            "variants pay one dispatch+sync per round; the "
            "device_worklist row runs the WHOLE fixpoint as one traced "
            "lax.while_loop dispatch (asserted == 1) with on-device "
            "frontier compaction."),
        "apps": {},
    }

    from repro.apps.pagerank import _pr_graph
    part = build_partition(gw, pcfg)
    part_pr = build_partition(_pr_graph(g), pcfg)

    # (name, semiring, partition, sources, fixed_rounds, delta_tol)
    jobs = [
        ("bfs", actions.BFS, part, {root: 0.0}, None, None),
        ("sssp", actions.SSSP, part, {root: 0.0}, None, None),
        ("pagerank", actions.PAGERANK, part_pr, {}, args.pr_iters, None),
        # same round count as dense pagerank -> apples-to-apples pruning
        ("pagerank_delta", actions.PAGERANK, part_pr, {}, args.pr_iters,
         args.pr_tol),
    ]
    for name, sem, p, sources, fixed, dtol in jobs:
        entry = {}
        slots = p.S * p.R_max
        variants = [
            ("fused", engine.EngineConfig(use_pallas=True),
             "grid_fused_live"),
            ("unfused",
             engine.EngineConfig(use_pallas=True, pallas_mode="reduce"),
             "grid_range_live"),
            ("jnp", engine.EngineConfig(use_pallas=False), None),
        ]
        if args.grid_mode != "dense":
            # the per-round host-planned variants need a host planner —
            # under --grid-mode device_worklist they keep planning with
            # 'worklist' and the device_worklist row below covers the
            # device-compacted whole-fixpoint dispatch
            host_mode = args.grid_mode \
                if args.grid_mode in ("worklist", "auto") else "worklist"
            variants += [
                ("worklist",
                 engine.EngineConfig(use_pallas=True,
                                     grid_mode=host_mode),
                 "grid_wl_cells"),
            ]
        for label, cfg, cell_key in variants:
            snap = disp_snap()
            rounds = bench_rounds(
                sem, p, sources, cfg, args.max_rounds, fixed_rounds=fixed,
                repeats=args.repeats, delta_tol=dtol,
                check_debug=label in ("worklist", "fused"))
            dd, ds = disp_delta(snap)
            entry[label] = summarize(rounds, cell_key)
            entry[label]["dispatches_total"] = int(dd)
            entry[label]["host_syncs_per_round"] = \
                ds / max(len(rounds), 1)
            print(f"{name:15s} {label:8s} "
                  f"rounds={entry[label]['rounds']:3d} "
                  f"wall/round={entry[label]['wall_s_per_round']*1e3:8.2f}ms "
                  f"msgs/s={entry[label]['messages_per_s']:.3e} "
                  f"cells={entry[label]['grid_cells_executed']}")
        if args.grid_mode != "dense" and name != "pagerank":
            # ISSUE-8 acceptance row: the whole fixpoint as ONE traced
            # dispatch, plus the device-compaction mirror assertion on
            # the first frontier (kernel with_debug == host mirror)
            if dtol is None:
                init = engine.init_values(p, sem, sources)
                arrays0 = engine.DeviceArrays.from_partition(p)
                val0 = jnp.asarray(init)
                chg0 = sem.improved(
                    val0, jnp.full_like(val0, sem.identity)) \
                    & arrays0.slot_valid
                _device_debug_check(p, sem, np.asarray(val0).reshape(-1),
                                    np.asarray(chg0).reshape(-1), slots)
            entry["device_worklist"] = bench_device_fixpoint(
                name, sem, p, sources, args.max_rounds
                if fixed is None else fixed, delta_tol=dtol)
            dw = entry["device_worklist"]
            print(f"{name:15s} {'device':8s} "
                  f"rounds={dw['rounds']:3d} "
                  f"wall/round={dw['wall_s_per_round']*1e3:8.2f}ms "
                  f"msgs/s={dw['messages_per_s']:.3e} "
                  f"dispatches={dw['dispatches_total']}")
        f, u = entry["fused"], entry["unfused"]
        if "worklist" in entry:
            wl = entry["worklist"]
            entry["worklist_vs_dense"] = {
                "cells_launched_worklist": wl["wl_launched_total"],
                "cells_live_worklist": wl["wl_cells_total"],
                "cells_executed_dense": f["grid_cells_executed"],
                "grid_total_dense":
                    sum(r["grid_total_fused"] for r in f["per_round"]),
                "wall_s_per_round_worklist": wl["wall_s_per_round"],
                "wall_s_per_round_dense": f["wall_s_per_round"],
            }
            if fixed is None and wl["per_round"]:
                late = wl["per_round"][-1]
                entry["late_round_worklist"] = {
                    "wl_cells": late["grid_wl_cells"],
                    "wl_launched": late["grid_wl_launched"],
                    "dense_grid": late["grid_total_fused"],
                    "dense_live": late["grid_fused_live"],
                }
        # the frontier skip must fire: strictly fewer grid cells on the
        # late sparse rounds of the frontier apps (incl. delta-PR)
        if fixed is None and f["per_round"]:
            late = f["per_round"][-1]
            entry["late_round_skip"] = {
                "fused_live": late["grid_fused_live"],
                "range_live": late["grid_range_live"],
                "skip_firing": late["grid_fused_live"]
                < late["grid_range_live"],
            }
        entry["grid_cell_reduction"] = (
            1.0 - f["grid_cells_executed"] / max(u["grid_cells_executed"], 1))
        report["apps"][name] = entry

    pr, prd = report["apps"]["pagerank"], report["apps"]["pagerank_delta"]
    report["delta_vs_dense_pagerank"] = {
        "rounds": (pr["fused"]["rounds"], prd["fused"]["rounds"]),
        "messages": (pr["fused"]["messages_total"],
                     prd["fused"]["messages_total"]),
        "grid_cells": (pr["fused"]["grid_cells_executed"],
                       prd["fused"]["grid_cells_executed"]),
        "delta_prunes": prd["fused"]["messages_total"]
        < pr["fused"]["messages_total"]
        and prd["fused"]["grid_cells_executed"]
        < pr["fused"]["grid_cells_executed"],
    }
    # the ISSUE-5 acceptance bar (strictly fewer messages AND cells)
    # holds whenever the residual frontier actually thinned a chunk
    # within the round budget — guaranteed at the committed BENCH
    # parameters (scale 10, 10 iters, pr-tol 3e-5); short/small runs may
    # prune messages before any whole edge chunk goes dead, so gate the
    # strict cell assert on the observed last-round frontier
    assert prd["fused"]["messages_total"] \
        <= pr["fused"]["messages_total"]
    last_delta = prd["fused"]["per_round"][-1]["grid_fused_live"]
    last_dense = pr["fused"]["per_round"][-1]["grid_fused_live"]
    if last_delta < last_dense:
        assert report["delta_vs_dense_pagerank"]["delta_prunes"], \
            report["delta_vs_dense_pagerank"]

    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2)
    print(f"wrote {args.out}")
    common.finish_report(report, obs_out=args.obs_out)


if __name__ == "__main__":
    main()
