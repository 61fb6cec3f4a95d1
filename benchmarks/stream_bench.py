"""Streaming-graph benchmark (ISSUE 9 tentpole).

Three legs against ``StreamingGraph`` (in-place partition splicing +
incremental result maintenance + adaptive rhizome growth):

* **incremental vs cold** — a 1%-of-edges insert batch on a fixed-seed
  RMAT: incremental BFS/SSSP/delta-PageRank maintenance (warm-started
  at the affected region) vs a cold fixpoint on the final graph, in
  exact engine counters — rounds, messages, live grid cells (the
  planner mirror).  The acceptance column: incremental does measurably
  fewer messages AND cells than cold on every app.
* **mutate-while-serving** — a ``QueryServer`` bound to the stream:
  interleaved query waves and mutation commits, reporting sustained
  mutations/s, queries/s, splice sizes, and cache invalidations.
* **staleness vs recompute cost** — the same mutation schedule applied
  with ``refresh_every ∈ {1, 4, 16}`` batches per maintenance commit:
  deferring maintenance amortizes warm-start cost (messages/commit)
  against result staleness (max |stale − fresh| PageRank error sampled
  between commits).

Usage:  PYTHONPATH=src python benchmarks/stream_bench.py [--out PATH]
        [--smoke]   # CI: tiny graph + assert incremental < cold
"""
from __future__ import annotations

import argparse
import json
import time

import common  # noqa: F401  (compile cache)
import numpy as np

from repro import obs
from repro.core import actions, engine
from repro.core.partition import PartitionConfig, build_partition
from repro.core.streaming import StreamingGraph, _pr_weights
from repro.graph import generators

PR_TOL = 1e-6


def _totals(rounds, run):
    rs = [r for r in rounds if r.run == run]
    return {"rounds": len(rs),
            "messages": sum(r.messages for r in rs),
            "cells": sum(r.cells for r in rs)}


def _build(scale: int, seed: int, shards: int):
    gw = generators.rmat(scale, edge_factor=6,
                         seed=seed).with_random_weights(seed=seed)
    pcfg = PartitionConfig(num_shards=shards, rpvo_max=4)
    cfg = engine.EngineConfig(use_pallas=True, grid_mode="dense")
    return gw, pcfg, cfg


# --------------------------------------------------------------------------
# leg 1: incremental vs cold after a 1%-edge insert batch
# --------------------------------------------------------------------------

def incremental_vs_cold(scale: int, seed: int, shards: int) -> dict:
    gw, pcfg, cfg = _build(scale, seed, shards)
    root = int(np.argmax(gw.out_degrees()))
    sg = StreamingGraph(gw, pcfg, cfg=cfg)
    sg.track("bfs", root)
    sg.track("sssp", root)
    sg.track("pagerank", tol=PR_TOL)

    rng = np.random.default_rng(seed)
    k = max(1, gw.num_edges // 100)          # the 1% batch
    s = rng.integers(0, gw.n, k).astype(np.int32)
    d = rng.integers(0, gw.n, k).astype(np.int32)
    w = rng.integers(1, 10, k).astype(np.float32)
    sg.insert_edges(s, d, w)
    with obs.recording() as rec:
        info = sg.commit()
    inc = {"bfs": _totals(rec.rounds, "bfs"),
           "sssp": _totals(rec.rounds, "sssp"),
           "pagerank": _totals(rec.rounds, "pagerank_delta")}

    part = sg.view("base").part
    part_pr = sg.view("pr").part
    with obs.recording() as rec:
        for name, sem in (("bfs", actions.BFS), ("sssp", actions.SSSP)):
            init = engine.init_values(part, sem, {root: 0.0})
            engine.run_stacked(sem, part, init, cfg)
        engine.run_pagerank_delta(part_pr, tol=PR_TOL, cfg=cfg)
    cold = {"bfs": _totals(rec.rounds, "bfs"),
            "sssp": _totals(rec.rounds, "sssp"),
            "pagerank": _totals(rec.rounds, "pagerank_delta")}

    sp = info.splices["base"]
    return {
        "graph": {"scale": scale, "n": gw.n,
                  "edges_before": gw.num_edges - 0,
                  "insert_batch": k, "root": root},
        "splice": {"shards_rebuilt": sp.shards_rebuilt,
                   "shards_total": sp.shards_total,
                   "replicas_added": sp.replicas_added,
                   "affected_edges": sp.affected_edges},
        "incremental": inc,
        "cold": cold,
        "ratio_messages": {
            app: (inc[app]["messages"] / max(cold[app]["messages"], 1))
            for app in inc},
        "ratio_cells": {
            app: (inc[app]["cells"] / max(cold[app]["cells"], 1))
            for app in inc},
    }


# --------------------------------------------------------------------------
# leg 2: sustained mutations interleaved with live queries
# --------------------------------------------------------------------------

def mutate_while_serving(scale: int, seed: int, shards: int,
                         batches: int, queries_per_batch: int) -> dict:
    from repro.query.server import QueryServer
    from repro.serve.admission import ServeConfig

    gw, pcfg, cfg = _build(scale, seed, shards)
    sg = StreamingGraph(gw, pcfg, cfg=cfg)
    srv = QueryServer(sg.view("base").part, n_lanes=4,
                      serve=ServeConfig(cache_size=32))
    sg.bind_server(srv)
    rng = np.random.default_rng(seed + 1)
    hubs = np.argsort(gw.out_degrees())[-16:]

    t0 = time.monotonic()
    mutated_edges = 0
    for b in range(batches):
        for _ in range(queries_per_batch):
            kind = ("bfs", "sssp")[int(rng.integers(0, 2))]
            srv.submit(kind, [int(rng.choice(hubs))])
        srv.run()
        k = 16
        s = rng.integers(0, gw.n, k).astype(np.int32)
        d = rng.integers(0, gw.n, k).astype(np.int32)
        sg.insert_edges(s, d, rng.integers(1, 10, k).astype(np.float32))
        if b % 2 == 1:
            idx = rng.choice(sg.g.num_edges, 8, replace=False)
            sg.delete_edges(sg.g.src[idx], sg.g.dst[idx])
            mutated_edges += 8
        sg.commit()
        mutated_edges += k
    wall = time.monotonic() - t0
    done = sum(1 for r in srv.results.values() if r.status == "ok")
    return {
        "batches": batches, "wall_s": wall,
        "mutated_edges": mutated_edges,
        "mutations_per_s": mutated_edges / max(wall, 1e-9),
        "queries_completed": done,
        "queries_per_s": done / max(wall, 1e-9),
        "cache_invalidations": int(srv.counters["cache_invalidations"]),
        "server_mutations": int(srv.counters["mutations"]),
    }


# --------------------------------------------------------------------------
# leg 3: staleness vs recompute cost
# --------------------------------------------------------------------------

def staleness_vs_cost(scale: int, seed: int, shards: int,
                      batches: int) -> dict:
    out = {}
    for refresh_every in (1, 4, 16):
        gw, pcfg, cfg = _build(scale, seed, shards)
        sg = StreamingGraph(gw, pcfg, cfg=cfg)
        sg.track("pagerank", tol=PR_TOL)
        rng = np.random.default_rng(seed + 2)
        cost_msgs = 0
        commits = 0
        stale_errs = []
        from repro.graph.graph import COOGraph
        true_g = gw
        for b in range(batches):
            k = 8
            s = rng.integers(0, gw.n, k).astype(np.int32)
            d = rng.integers(0, gw.n, k).astype(np.int32)
            w = rng.integers(1, 10, k).astype(np.float32)
            sg.insert_edges(s, d, w)
            true_g = COOGraph(true_g.n,
                              np.concatenate([true_g.src, s]),
                              np.concatenate([true_g.dst, d]),
                              np.concatenate([true_g.weight, w]))
            if (b + 1) % refresh_every == 0:
                info = sg.commit()
                commits += 1
                cost_msgs += info.maint[("pagerank", None)].messages
            else:
                # stale window: measure the served (old) ranks against a
                # fresh fixpoint on the would-be graph
                part_pr = build_partition(_pr_weights(true_g), sg.pcfg)
                rank_t, _ = engine.run_pagerank_delta(
                    part_pr, tol=PR_TOL, cfg=engine.EngineConfig())
                fresh = engine.vertex_values(part_pr, rank_t)
                stale_errs.append(float(np.abs(
                    sg.values("pagerank") - fresh).max()))
        out[f"refresh_every_{refresh_every}"] = {
            "commits": commits,
            "maintenance_messages": cost_msgs,
            "messages_per_commit": cost_msgs / max(commits, 1),
            "stale_batches": len(stale_errs),
            "staleness_max": max(stale_errs, default=0.0),
            "staleness_mean": (float(np.mean(stale_errs))
                               if stale_errs else 0.0),
        }

    # --- staleness-SLO row: instead of a fixed cadence, the stream
    # auto-commits whenever the pending-mutation staleness bound
    # crosses the SLO (deferred commits with a bounded stale window)
    slo = 2.5 * 8                          # ~2.5 batches of 8 edges
    gw, pcfg, cfg = _build(scale, seed, shards)
    sg = StreamingGraph(gw, pcfg, cfg=cfg, staleness_slo=slo)
    sg.track("pagerank", tol=PR_TOL)
    rng = np.random.default_rng(seed + 2)
    cost_msgs = 0
    with obs.recording() as rec:
        for b in range(batches):
            k = 8
            s = rng.integers(0, gw.n, k).astype(np.int32)
            d = rng.integers(0, gw.n, k).astype(np.int32)
            w = rng.integers(1, 10, k).astype(np.float32)
            sg.insert_edges(s, d, w)
    cost_msgs = sum(r.messages for r in rec.rounds
                    if r.run == "pagerank_delta")
    out["auto_slo"] = {
        "staleness_slo": slo,
        "commits": sg.auto_refreshes,
        "auto_refreshes": sg.auto_refreshes,
        "maintenance_messages": cost_msgs,
        "messages_per_commit": cost_msgs / max(sg.auto_refreshes, 1),
        "residual_staleness": sg.staleness(),
    }
    return out


# --------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_stream.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI: tiny graph; assert incremental < cold")
    common.add_seed_arg(ap)
    common.add_obs_out_arg(ap)
    args = ap.parse_args(argv)

    scale = 7 if args.smoke else 9
    shards = 4 if args.smoke else 8
    report = {"bench": "stream", "seed": args.seed, "smoke": args.smoke}

    print(f"incremental vs cold (scale {scale}, 1% insert batch) ...")
    leg1 = incremental_vs_cold(scale, args.seed, shards)
    report["incremental_vs_cold"] = leg1
    for app in ("bfs", "sssp", "pagerank"):
        inc = leg1["incremental"][app]
        cold = leg1["cold"][app]
        print(f"  {app:>8}: messages {inc['messages']} vs {cold['messages']}"
              f" ({leg1['ratio_messages'][app]:.3f}x), cells"
              f" {inc['cells']} vs {cold['cells']}"
              f" ({leg1['ratio_cells'][app]:.3f}x)")
        # the acceptance criterion: strictly fewer messages AND cells
        # on the insert schedule (hard-asserted in the CI smoke leg)
        if args.smoke:
            assert inc["messages"] < cold["messages"], app
            assert inc["cells"] < cold["cells"], app

    print("mutate while serving ...")
    batches = 4 if args.smoke else 12
    leg2 = mutate_while_serving(scale, args.seed, shards, batches, 4)
    report["mutate_while_serving"] = leg2
    print(f"  {leg2['mutations_per_s']:.0f} edge-mutations/s, "
          f"{leg2['queries_per_s']:.1f} queries/s, "
          f"{leg2['queries_completed']} queries over {batches} batches")

    print("staleness vs recompute cost ...")
    leg3 = staleness_vs_cost(6 if args.smoke else 7, args.seed, 4,
                             8 if args.smoke else 16)
    report["staleness_vs_cost"] = leg3
    for key, row in leg3.items():
        if key == "auto_slo":
            print(f"  {key}: {row['maintenance_messages']} msgs over "
                  f"{row['auto_refreshes']} auto-refreshes "
                  f"(slo {row['staleness_slo']}, residual "
                  f"{row['residual_staleness']})")
        else:
            print(f"  {key}: {row['maintenance_messages']} msgs over "
                  f"{row['commits']} commits, staleness max "
                  f"{row['staleness_max']:.2e}")

    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2)
    print(f"wrote {args.out}")
    common.finish_report(report, obs_out=args.obs_out)


if __name__ == "__main__":
    main()
