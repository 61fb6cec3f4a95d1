"""Counter-regression gate: exact engine counters on a tiny CI graph.

Runs BFS, SSSP, and delta-PageRank on a fixed-seed RMAT partition with
the flight recorder installed and compares the *exact* per-run totals —
rounds, messages, pruned deliveries, live grid cells — against
the committed baselines in ``benchmarks/baselines/counter_gate.json``.
Any drift in message counts or planner-mirror grid accounting (the
numbers PRs 4–7 assert equal to the kernels' ``with_debug`` counters)
fails CI with a field-level diff, so a perf "optimization" that silently
changes how much work the engine does cannot land unnoticed.

Wall-clock never participates: the gate compares only deterministic
counters, so it is stable across machines.

Usage::

    python benchmarks/counter_gate.py            # compare (CI)
    python benchmarks/counter_gate.py --update   # rewrite baselines
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import common  # noqa: F401  (compile cache)
import numpy as np

from repro import obs
from repro.core import actions, engine
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators

BASELINE = pathlib.Path(__file__).parent / "baselines" / "counter_gate.json"

# deliberately tiny: the gate must run in CI seconds, and the counters
# are exact at any scale
SCALE, EDGE_FACTOR, SEED = 8, 8, 7
SHARDS, RPVO_MAX = 4, 4
PR_ITERS, PR_TOL = 8, 3e-5


def _totals(rounds, run):
    rs = [r for r in rounds if r.run == run]
    return {
        "rounds": len(rs),
        "frontier_first": rs[0].frontier if rs else 0,
        "messages": sum(r.messages for r in rs),
        "pruned": sum(r.pruned for r in rs),
        "cells": sum(r.cells for r in rs),
        "launched": sum(r.launched for r in rs),
        "shard_messages": [sum(col) for col in zip(
            *(r.shard_messages for r in rs))] if rs else [],
    }


def run_gate() -> dict:
    g = generators.rmat(SCALE, edge_factor=EDGE_FACTOR, seed=SEED)
    gw = g.with_random_weights(seed=SEED)
    root = int(np.argmax(g.out_degrees()))
    pcfg = PartitionConfig(num_shards=SHARDS, rpvo_max=RPVO_MAX)
    part = build_partition(gw, pcfg)

    from repro.apps.pagerank import _pr_graph
    part_pr = build_partition(_pr_graph(g), pcfg)

    out = {"graph": {"scale": SCALE, "edge_factor": EDGE_FACTOR,
                     "seed": SEED, "n": g.n, "num_edges": g.num_edges,
                     "root": root},
           "runs": {}}
    with obs.recording() as rec:
        for name, sem in (("bfs", actions.BFS), ("sssp", actions.SSSP)):
            # device_worklist records per-WINDOW rows (rounds = window
            # count); its additive counters must stay exactly equal to
            # the host-driven runs' totals, so the gate pins all three
            for grid in ("dense", "worklist", "device_worklist"):
                cfg = engine.EngineConfig(use_pallas=True, grid_mode=grid)
                init = engine.init_values(part, sem, {root: 0.0})
                engine.run_stacked(sem, part, init, cfg)
                key = f"{name}_{grid}"
                out["runs"][key] = _totals(rec.rounds, sem.name)
                rec.rounds.clear()
        engine.run_pagerank_delta(
            part_pr, tol=PR_TOL, max_rounds=PR_ITERS,
            cfg=engine.EngineConfig(use_pallas=True, grid_mode="auto"))
        out["runs"]["pagerank_delta"] = _totals(rec.rounds,
                                                "pagerank_delta")
        rec.rounds.clear()
        engine.run_pagerank_delta(
            part_pr, tol=PR_TOL, max_rounds=PR_ITERS,
            cfg=engine.EngineConfig(use_pallas=True,
                                    grid_mode="device_worklist"))
        out["runs"]["pagerank_delta_device"] = _totals(
            rec.rounds, "pagerank_delta")

    out["runs"].update(_stream_leg(gw))
    out["runs"].update(_resilient_leg(gw, part, root))
    return out


def _resilient_leg(gw, part, root) -> dict:
    """Kill-and-restore leg (ISSUE 10): a shard killed at round 3, the
    resilient driver restores from the round-2 checkpoint, and the
    POST-RECOVERY totals — rounds, messages, work — are pinned EQUAL to
    the uninterrupted run's (counters ride in the checkpoint tree, so
    recovery is invisible in the accounting)."""
    import tempfile

    from repro.core.resilient import StackedTask, run_resilient
    from repro.checkpoint.manager import CheckpointManager
    from repro.runtime.chaos import ChaosEvent, ChaosPlan

    cfg = engine.EngineConfig(checkpoint_every=2)
    init = engine.init_values(part, actions.SSSP, {root: 0.0})
    base_val, base_stats = engine.run_stacked(
        actions.SSSP, part, init, engine.EngineConfig())
    chaos = ChaosPlan(events=(
        ChaosEvent(round=3, kind="kill_shard", shard=1),))
    with tempfile.TemporaryDirectory() as d:
        got, stats, report = run_resilient(
            StackedTask(actions.SSSP, part, init, cfg), chaos=chaos,
            manager=CheckpointManager(d))
    return {"resilient_kill_restore": {
        "status": report.status,
        "faults": len(report.faults),
        "restores": report.restores,
        "rounds_lost": report.rounds_lost,
        "checkpoints_written": report.checkpoints_written,
        "rounds": int(stats.iterations),
        "messages": int(stats.messages),
        "work": int(stats.work_actions),
        "equal_uninterrupted": bool(
            int(stats.iterations) == int(base_stats.iterations)
            and int(stats.messages) == int(base_stats.messages)
            and int(stats.work_actions) == int(base_stats.work_actions)
            and np.array_equal(np.asarray(got), np.asarray(base_val))),
    }}


def _stream_leg(gw) -> dict:
    """Streaming leg: a FIXED mutation schedule on the same scale-8
    RMAT; pins the incremental-maintenance message/cell counters (the
    warm-start fixpoints the ISSUE 9 splice path drives) so a change
    that silently re-lifts more than the affected region fails CI."""
    from repro.core.streaming import StreamingGraph

    root = int(np.argmax(gw.out_degrees()))
    pcfg = PartitionConfig(num_shards=SHARDS, rpvo_max=RPVO_MAX)
    cfg = engine.EngineConfig(use_pallas=True, grid_mode="dense")
    sg = StreamingGraph(gw, pcfg, cfg=cfg)
    sg.track("bfs", root)
    sg.track("sssp", root)
    rng = np.random.default_rng(SEED)
    out = {}
    with obs.recording() as rec:
        for batch in range(2):
            s = rng.integers(0, gw.n, 16).astype(np.int32)
            d = rng.integers(0, gw.n, 16).astype(np.int32)
            w = rng.integers(1, 10, 16).astype(np.float32)
            sg.insert_edges(s, d, w)
            if batch == 1:
                idx = rng.choice(sg.g.num_edges, 8, replace=False)
                sg.delete_edges(sg.g.src[idx], sg.g.dst[idx])
            info = sg.commit()
            for name in ("bfs", "sssp"):
                key = f"stream_{name}_batch{batch}"
                out[key] = _totals(rec.rounds, name)
                ms = info.maint[(name, root)]
                out[key]["maint_messages"] = ms.messages
                out[key]["seeds"] = ms.seeds
                out[key]["invalidated"] = ms.invalidated
            sp = info.splices["base"]
            out[f"stream_splice_batch{batch}"] = {
                "shards_rebuilt": sp.shards_rebuilt,
                "replicas_added": sp.replicas_added,
                "replicas_moved": sp.replicas_moved,
                "affected_edges": sp.affected_edges,
            }
            rec.rounds.clear()
    return out


def diff(base: dict, got: dict, path="") -> list[str]:
    errs = []
    if isinstance(base, dict) and isinstance(got, dict):
        for k in sorted(set(base) | set(got)):
            if k not in base or k not in got:
                errs.append(f"{path}/{k}: only in "
                            f"{'baseline' if k in base else 'run'}")
            else:
                errs.extend(diff(base[k], got[k], f"{path}/{k}"))
    elif base != got:
        errs.append(f"{path}: baseline {base!r} != run {got!r}")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--update", action="store_true",
                    help="rewrite the committed baselines from this run")
    args = ap.parse_args(argv)

    got = run_gate()
    if args.update:
        BASELINE.parent.mkdir(parents=True, exist_ok=True)
        with open(BASELINE, "w") as fh:
            json.dump(got, fh, indent=1, sort_keys=True)
        print(f"wrote {BASELINE}")
        return 0

    if not BASELINE.exists():
        print(f"missing baseline {BASELINE}; run with --update", flush=True)
        return 2
    with open(BASELINE) as fh:
        base = json.load(fh)
    errs = diff(base, got)
    if errs:
        print("counter gate FAILED — exact-counter drift:")
        for e in errs:
            print("  " + e)
        return 1
    n = len(base["runs"])
    msgs = sum(r.get("messages", 0) for r in base["runs"].values())
    print(f"counter gate OK: {n} runs, {msgs} messages, all counters exact")
    return 0


if __name__ == "__main__":
    sys.exit(main())
