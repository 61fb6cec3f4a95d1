"""Overload-safe serving load harness (ISSUE 6 tentpole).

Two-phase load generator against the robustness-wrapped ``QueryServer``:

* **closed loop** — keeps the lane pools saturated (queue topped up to
  2x lanes, unbounded) to measure service capacity: queries/s and the
  per-tick completion rate that calibrates the open-loop arrival rates;
* **open loop** — Poisson arrivals at 1x / 2x / 4x the measured
  capacity against a bounded queue under the 'reject' and 'shed'
  overload policies, with a mixed BFS/SSSP/PPR workload over a zipfian
  root distribution (cache-friendly repeats), per-request deadlines on a
  slice of the traffic, round budgets on another, and two weighted
  tenants.  Reports p50/p99 latency, queries/s, shed rate, deadline /
  timeout / budget counts, cache hit rate, and the maximum queue depth
  (bounded by construction — the acceptance criterion).
* **faults** — a fault-injected leg (induced lane failure + delayed
  tick) proving failure paths resolve as typed statuses mid-load.

Every leg asserts the zero-uncaught-exception criterion: each submitted
request resolves to exactly one typed terminal status, i.e.
``counters['submitted'] == sum(terminal counters)`` — the consistency
check the CI smoke leg pins at 2x overload.

Usage:  PYTHONPATH=src python benchmarks/serve_bench.py [--out PATH]
        [--smoke]      # CI: tiny graph, pinned seed, 2x overload only
"""
from __future__ import annotations

import argparse
import json
import time

import common  # compile cache; --seed helper
import numpy as np

from repro.apps.pagerank import _pr_graph
from repro.core import engine
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators
from repro.query import FaultPlan, QueryServer, QueryStatus, ServeConfig

TERMINAL = sorted(QueryStatus.TERMINAL)


def build_part(log2_nodes: int, seed: int):
    g = generators.rmat(log2_nodes, edge_factor=6,
                        seed=seed).with_random_weights(seed=seed)
    num_shards = 4 if log2_nodes <= 10 else 8
    part = build_partition(_pr_graph(g),
                           PartitionConfig(num_shards=num_shards,
                                           rpvo_max=4))
    return g, part


class Workload:
    """Deterministic mixed-kind request stream: zipfian roots over the
    high-degree vertices (repeats -> cache hits), 60/20/20
    bfs/sssp/ppr, deadlines on a quarter of the traffic, round budgets
    on a tenth, two tenants at 2:1 weight."""

    def __init__(self, g, seed: int, deadline_s: float, n_roots: int = 64):
        self.rng = np.random.default_rng(seed)
        deg = np.argsort(-g.out_degrees())
        self.roots = deg[:n_roots].astype(int)
        self.deadline_s = deadline_s

    def next(self):
        r = self.rng
        root = int(self.roots[min(r.geometric(0.25) - 1,
                                  len(self.roots) - 1)])
        u = r.random()
        kind = "bfs" if u < 0.6 else ("sssp" if u < 0.8 else "ppr")
        kw = dict(tenant="gold" if r.random() < 0.33 else "free",
                  priority=2 if r.random() < 0.15 else 0)
        if r.random() < 0.25:
            kw["deadline_s"] = self.deadline_s
        if r.random() < 0.10:
            kw["max_rounds"] = 4
        return kind, root, kw


def submit_safe(srv, kind, root, kw, errors):
    """The zero-uncaught-exception harness: any exception escaping a
    policed submit is an acceptance failure, recorded not raised."""
    try:
        srv.submit(kind, root, **kw)
    except Exception as e:          # noqa: BLE001 — the bench's whole point
        errors.append(f"{kind}@{root}: {type(e).__name__}: {e}")


def consistency(srv) -> dict:
    """Each submitted request resolved to exactly one terminal status."""
    terminal_total = sum(srv.counters[s] for s in TERMINAL)
    return {
        "submitted": srv.counters["submitted"],
        "terminal_total": terminal_total,
        "results": len(srv.results),
        "consistent": (srv.counters["submitted"] == terminal_total
                       == len(srv.results)),
    }


def summarize(srv, wall_s: float, max_qlen: int) -> dict:
    res = srv.results.values()
    lat_ok = np.array([r.latency_s for r in res
                       if r.status == QueryStatus.OK]) * 1e3
    c = srv.counters
    submitted = c["submitted"]
    dropped = c[QueryStatus.REJECTED] + c[QueryStatus.SHED]
    cache_lookups = c["cache_hits"] + c["cache_misses"]
    out = {
        "submitted": submitted,
        "completed_ok": int(c[QueryStatus.OK]),
        "qps": c[QueryStatus.OK] / max(wall_s, 1e-9),
        "p50_ms": float(np.percentile(lat_ok, 50)) if len(lat_ok) else None,
        "p99_ms": float(np.percentile(lat_ok, 99)) if len(lat_ok) else None,
        "shed_rate": dropped / max(submitted, 1),
        "cache_hit_rate": (c["cache_hits"] / cache_lookups
                           if cache_lookups else 0.0),
        "max_queue_len": max_qlen,
        "ticks": srv.tick,
        "preemptions": int(c["preemptions"]),
        "statuses": {s: int(c[s]) for s in TERMINAL if c[s]},
        "consistency": consistency(srv),
    }
    return out


def closed_loop(part, wl: Workload, lanes: int, n_queries: int) -> dict:
    """Saturation throughput: the queue is topped up to 2x lanes every
    tick, so the pools never starve — service capacity, not latency."""
    srv = QueryServer(part, n_lanes=lanes, ppr_lanes=max(lanes // 2, 1))
    errors: list[str] = []
    submitted = 0
    t0 = time.perf_counter()
    while srv.counters[QueryStatus.OK] < n_queries:
        while submitted < n_queries and len(srv.queue) < 2 * lanes:
            kind, root, kw = wl.next()
            kw.pop("deadline_s", None)    # capacity probe: no drops
            kw.pop("max_rounds", None)
            submit_safe(srv, kind, root, kw, errors)
            submitted += 1
        srv.step()
    wall = time.perf_counter() - t0
    assert not errors, errors
    return {
        "completed": int(srv.counters[QueryStatus.OK]),
        "ticks": srv.tick,
        "qps": srv.counters[QueryStatus.OK] / wall,
        "service_per_tick": srv.counters[QueryStatus.OK] / max(srv.tick, 1),
        "occupancy": srv.occupancy(),
        "consistency": consistency(srv),
    }


def open_loop(part, wl: Workload, lanes: int, policy: str, overload: float,
              service_per_tick: float, n_ticks: int,
              faults: FaultPlan | None = None) -> dict:
    """Poisson arrivals at ``overload`` x measured capacity against a
    bounded queue; after the arrival window the server drains."""
    serve = ServeConfig(max_queue=2 * lanes, overload_policy=policy,
                        cache_size=64, cache_ttl_s=None, faults=faults)
    srv = QueryServer(part, n_lanes=lanes, ppr_lanes=max(lanes // 2, 1),
                      serve=serve)
    lam = overload * service_per_tick
    errors: list[str] = []
    max_qlen = 0
    t0 = time.perf_counter()
    for _ in range(n_ticks):
        for _ in range(int(wl.rng.poisson(lam))):
            kind, root, kw = wl.next()
            submit_safe(srv, kind, root, kw, errors)
        max_qlen = max(max_qlen, len(srv.queue))
        srv.step()
    srv.run()                                  # drain the tail
    wall = time.perf_counter() - t0
    out = summarize(srv, wall, max_qlen)
    out["errors"] = errors
    out["bounded"] = max_qlen <= serve.max_queue
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nodes", type=int, default=10,
                    help="log2 graph vertices (default 10)")
    ap.add_argument("--lanes", type=int, default=6)
    ap.add_argument("--closed-queries", type=int, default=48)
    ap.add_argument("--ticks", type=int, default=160,
                    help="open-loop arrival window, in server ticks")
    ap.add_argument("--deadline-ms", type=float, default=400.0)
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI leg: tiny graph, 2x overload only, hard "
                         "consistency assertions")
    common.add_seed_arg(ap)
    common.add_obs_out_arg(ap)
    args = ap.parse_args()
    if args.smoke:
        args.nodes, args.lanes = min(args.nodes, 9), 4
        args.closed_queries, args.ticks = 24, 60

    g, part = build_part(args.nodes, args.seed)
    report = {
        "bench": "serve", "seed": args.seed, "lanes": args.lanes,
        "graph": {"n": int(g.n), "m": int(len(g.src))},
        "partition": {"S": part.S, "R_max": part.R_max},
        "smoke": bool(args.smoke),
    }

    wl = Workload(g, args.seed, args.deadline_ms / 1e3)
    print(f"closed loop: {args.closed_queries} queries, "
          f"{args.lanes} lanes ...")
    closed = closed_loop(part, wl, args.lanes, args.closed_queries)
    assert closed["consistency"]["consistent"], closed["consistency"]
    report["closed_loop"] = closed
    spt = closed["service_per_tick"]
    print(f"  capacity {closed['qps']:.1f} q/s, "
          f"{spt:.3f} completions/tick")

    overloads = [2.0] if args.smoke else [1.0, 2.0, 4.0]
    report["open_loop"] = {}
    for policy in ("reject", "shed"):
        report["open_loop"][policy] = {}
        for ov in overloads:
            leg = open_loop(part, wl, args.lanes, policy, ov, spt,
                            args.ticks)
            key = f"{ov:g}x"
            report["open_loop"][policy][key] = leg
            assert not leg["errors"], leg["errors"]
            assert leg["consistency"]["consistent"], leg["consistency"]
            assert leg["bounded"], "queue exceeded its bound"
            print(f"  {policy:>6} {key}: p50={leg['p50_ms']:.0f}ms "
                  f"p99={leg['p99_ms']:.0f}ms shed={leg['shed_rate']:.2f} "
                  f"cache={leg['cache_hit_rate']:.2f} "
                  f"qlen<={leg['max_queue_len']}")

    # overload must actually shed under a bounded queue (acceptance:
    # nonzero shed rate at 4x; the smoke leg pins consistency at 2x)
    if not args.smoke:
        top = f"{overloads[-1]:g}x"
        for policy in ("reject", "shed"):
            assert report["open_loop"][policy][top]["shed_rate"] > 0, \
                f"no shedding at {top} under {policy!r}"

    # fault-injection leg: induced lane failure + delayed tick mid-load
    plan = FaultPlan(lane_failures=((3, "min", 0), (5, "ppr", 0)),
                     tick_delays=((4, args.deadline_ms / 1e3),))
    fault_leg = open_loop(part, wl, args.lanes, "reject", 2.0, spt,
                          max(args.ticks // 2, 30), faults=plan)
    assert not fault_leg["errors"], fault_leg["errors"]
    assert fault_leg["consistency"]["consistent"], fault_leg["consistency"]
    report["faults"] = fault_leg
    print(f"  faults: statuses={fault_leg['statuses']}")

    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2)
    print(f"wrote {args.out}")
    common.finish_report(report, obs_out=args.obs_out)


if __name__ == "__main__":
    main()
