"""Chaos benchmark (ISSUE 10): recovery cost under injected faults.

Four legs against the crash-safe fixpoint stack:

* **fault matrix** — a seeded random ``ChaosPlan`` (kills, corruptions,
  dropped/duplicated inboxes, delays at randomized rounds) against the
  resilient SSSP driver.  HARD assertion: every injected fault resolves
  to a typed terminal status ('ok' for benign/straggler faults,
  'recovered', or 'degraded') with min-semiring values BIT-equal to a
  fault-free oracle whenever the run was not degraded.  Columns:
  recovery wall time, retries/restores, rounds lost.
* **checkpoint cadence** — kill at a fixed round under
  ``checkpoint_every ∈ {off, 1, 4, 16}``: rounds lost to replay vs
  checkpoint write overhead per round (the paper-standard
  recovery-cost/steady-state-cost trade).
* **serving kill-and-restore** — a ``QueryServer`` snapshot at a
  commit (tick) boundary, killed mid-flight and warm-booted from the
  checkpoint: restore wall time and a hard equality check of every
  query's values/rounds/messages against an uninterrupted server.
* **streaming WAL replay** — a mutation batch checkpointed in the
  write-ahead log, crashed before ``commit()``, restored and replayed:
  tracked min values must be bit-equal to an uninterrupted commit.

Usage:  PYTHONPATH=src python benchmarks/chaos_bench.py [--out PATH]
        [--smoke]   # CI: tiny graph, fewer events, same assertions
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import common  # noqa: F401  (compile cache)
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core import actions, engine
from repro.core.partition import PartitionConfig, build_partition
from repro.core.resilient import StackedTask, run_resilient
from repro.core.streaming import StreamingGraph
from repro.graph import generators
from repro.runtime.chaos import ChaosEvent, ChaosPlan, RecoveryPolicy


def _case(scale: int, seed: int, shards: int, grid_mode: str):
    g = generators.rmat(scale, edge_factor=6,
                        seed=seed).with_random_weights(seed=seed)
    part = build_partition(g, PartitionConfig(num_shards=shards,
                                              rpvo_max=2))
    root = int(np.argmax(g.out_degrees()))
    cfg = engine.EngineConfig(use_pallas=(grid_mode != "dense"),
                              grid_mode=grid_mode)
    init = engine.init_values(part, actions.SSSP, {root: 0.0})
    return g, part, root, cfg, init


# --------------------------------------------------------------------------
# leg 1: the randomized fault matrix
# --------------------------------------------------------------------------

def fault_matrix(scale: int, seed: int, shards: int, grid_mode: str,
                 n_plans: int, events_per_plan: int) -> dict:
    g, part, root, cfg, init = _case(scale, seed, shards, grid_mode)
    oracle, ostats = engine.run_stacked(actions.SSSP, part, init, cfg)
    oracle_h = np.asarray(oracle)
    max_round = max(int(ostats.iterations) - 1, 2)

    rows = []
    by_status = {"ok": 0, "recovered": 0, "degraded": 0}
    for p in range(n_plans):
        chaos = ChaosPlan.random(seed=seed + 100 + p,
                                 n_events=events_per_plan,
                                 max_round=max_round, num_shards=shards)
        policy = RecoveryPolicy(max_retries=2,
                                max_restores=2 * events_per_plan)
        t0 = time.perf_counter()
        got, stats, report = run_resilient(
            StackedTask(actions.SSSP, part, init, cfg), chaos=chaos,
            policy=policy)
        wall = time.perf_counter() - t0
        # HARD assertions: typed terminal status; oracle-equal values
        # and accounting totals for every non-degraded run
        assert report.status in ("ok", "recovered", "degraded"), \
            report.status
        if report.status != "degraded":
            np.testing.assert_array_equal(np.asarray(got), oracle_h)
            assert int(stats.messages) == int(ostats.messages)
            assert int(stats.iterations) == int(ostats.iterations)
        by_status[report.status] += 1
        rows.append({
            "plan_seed": seed + 100 + p,
            "events": [[e.round, e.kind, e.shard] for e in chaos.events],
            "status": report.status,
            "faults_detected": len(report.faults),
            "retries": report.retries,
            "restores": report.restores,
            "rounds_lost": report.rounds_lost,
            "recovery_s": report.recovery_s,
            "wall_s": wall,
        })
    return {
        "oracle_rounds": int(ostats.iterations),
        "oracle_messages": int(ostats.messages),
        "plans": rows,
        "by_status": by_status,
        "recovery_s_mean": float(np.mean([r["recovery_s"]
                                          for r in rows])),
        "rounds_lost_mean": float(np.mean([r["rounds_lost"]
                                           for r in rows])),
    }


# --------------------------------------------------------------------------
# leg 2: rounds lost / write overhead vs checkpoint cadence
# --------------------------------------------------------------------------

def checkpoint_cadence(scale: int, seed: int, shards: int,
                       grid_mode: str, ckptdir: str) -> dict:
    g, part, root, cfg0, init = _case(scale, seed, shards, grid_mode)
    oracle, ostats = engine.run_stacked(actions.SSSP, part, init, cfg0)
    oracle_h = np.asarray(oracle)
    kill_round = max(int(ostats.iterations) - 2, 3)

    out = {"kill_round": kill_round,
           "oracle_rounds": int(ostats.iterations)}
    for K in (None, 1, 4, 16):
        import dataclasses
        cfg = dataclasses.replace(cfg0, checkpoint_every=K)
        mgr = (CheckpointManager(f"{ckptdir}/K{K}")
               if K is not None else None)
        chaos = ChaosPlan(events=(
            ChaosEvent(round=kill_round, kind="kill_shard", shard=1),))
        t0 = time.perf_counter()
        got, stats, report = run_resilient(
            StackedTask(actions.SSSP, part, init, cfg), chaos=chaos,
            manager=mgr)
        wall = time.perf_counter() - t0
        assert report.status == "recovered"
        np.testing.assert_array_equal(np.asarray(got), oracle_h)
        assert int(stats.messages) == int(ostats.messages)
        rounds = max(int(stats.iterations), 1)
        out[f"checkpoint_every_{'off' if K is None else K}"] = {
            "rounds_lost": report.rounds_lost,
            "checkpoints_written": report.checkpoints_written,
            "checkpoint_write_s": report.checkpoint_write_s,
            "checkpoint_write_s_per_round":
                report.checkpoint_write_s / rounds,
            "recovery_s": report.recovery_s,
            "wall_s": wall,
        }
    return out


# --------------------------------------------------------------------------
# leg 3: serving kill-and-restore at a commit boundary
# --------------------------------------------------------------------------

def serving_kill_restore(scale: int, seed: int, shards: int,
                         ckptdir: str) -> dict:
    from repro.query import QueryServer
    from repro.serve.admission import QueryStatus, ServeConfig

    g = generators.rmat(scale, edge_factor=5,
                        seed=seed).with_random_weights(seed=seed)
    part = build_partition(g, PartitionConfig(num_shards=shards,
                                              rpvo_max=2))
    roots = [int(r) for r in np.argsort(-g.out_degrees())[:6]]

    def submit_all(srv):
        qs = []
        for i, r in enumerate(roots):
            qs.append(srv.submit("bfs" if i % 2 else "sssp", r))
        return qs

    oracle = QueryServer(part, n_lanes=3)
    oq = submit_all(oracle)
    ores = oracle.run()

    serve = ServeConfig(checkpoint_every=2)
    srv = QueryServer(part, n_lanes=3, serve=serve)
    qs = submit_all(srv)
    srv.attach_checkpoints(CheckpointManager(f"{ckptdir}/serve"))
    kill_tick = 4
    for _ in range(kill_tick):
        srv.step()
    in_flight = sum(1 for q in qs if q not in srv.results)
    del srv                                  # crash

    t0 = time.perf_counter()
    srv2 = QueryServer.restore(part, CheckpointManager(f"{ckptdir}/serve"),
                               serve=serve)
    restore_s = time.perf_counter() - t0
    res = srv2.run()

    recovered = 0
    for q, oq_ in zip(qs, oq):
        o, r = ores[oq_], res[q]
        np.testing.assert_array_equal(np.asarray(r.values),
                                      np.asarray(o.values))
        assert r.rounds == o.rounds and r.messages == o.messages
        recovered += r.status == QueryStatus.RECOVERED
    return {
        "queries": len(qs),
        "kill_tick": kill_tick,
        "in_flight_at_kill": in_flight,
        "recovered_statuses": recovered,
        "restore_s": restore_s,
        "all_values_equal_oracle": True,     # asserted above
    }


# --------------------------------------------------------------------------
# leg 4: streaming WAL replay across a crash-mid-commit
# --------------------------------------------------------------------------

def streaming_wal_replay(scale: int, seed: int, shards: int,
                         ckptdir: str) -> dict:
    g = generators.rmat(scale, edge_factor=5, seed=seed)
    pcfg = PartitionConfig(num_shards=shards, rpvo_max=2)
    rng = np.random.default_rng(seed)
    k = max(8, g.num_edges // 50)
    ins = (rng.integers(0, g.n, k).astype(np.int32),
           rng.integers(0, g.n, k).astype(np.int32),
           (rng.random(k) + 0.1).astype(np.float32))

    def make():
        sg = StreamingGraph(g, pcfg)
        sg.track("bfs", 0)
        sg.track("sssp", 1)
        return sg

    oracle = make()
    oracle.insert_edges(*ins)
    oracle.commit()

    sg = make()
    sg.insert_edges(*ins)
    mgr = CheckpointManager(f"{ckptdir}/wal")
    t0 = time.perf_counter()
    sg.save_checkpoint(mgr, blocking=True)
    ckpt_s = time.perf_counter() - t0
    del sg                                   # crash mid-commit

    t0 = time.perf_counter()
    sg2 = StreamingGraph.restore(mgr)
    restore_s = time.perf_counter() - t0
    assert sg2._pending_ins, "WAL lost the uncommitted batch"
    sg2.commit()                             # replay
    for key in oracle.tracked:
        np.testing.assert_array_equal(
            np.asarray(oracle.tracked[key]["vals"]),
            np.asarray(sg2.tracked[key]["vals"]))
    return {
        "wal_edges": int(k),
        "checkpoint_s": ckpt_s,
        "restore_s": restore_s,
        "replay_exact": True,                # asserted above
    }


# --------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_chaos.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI: tiny graphs, fewer plans, same assertions")
    common.add_seed_arg(ap)
    common.add_grid_mode_arg(ap, default="dense")
    common.add_obs_out_arg(ap)
    args = ap.parse_args(argv)

    scale = 7 if args.smoke else 9
    shards = 4 if args.smoke else 8
    n_plans = 3 if args.smoke else 8
    events = 2 if args.smoke else 4
    report = {"bench": "chaos", "seed": args.seed, "smoke": args.smoke,
              "grid_mode": args.grid_mode}

    with tempfile.TemporaryDirectory() as ckptdir:
        print(f"fault matrix ({n_plans} random plans x {events} events, "
              f"scale {scale}, grid {args.grid_mode}) ...")
        leg1 = fault_matrix(scale, args.seed, shards, args.grid_mode,
                            n_plans, events)
        report["fault_matrix"] = leg1
        print(f"  statuses {leg1['by_status']}, mean recovery "
              f"{leg1['recovery_s_mean'] * 1e3:.1f} ms, mean rounds lost "
              f"{leg1['rounds_lost_mean']:.1f}")

        print("checkpoint cadence (kill at fixed round) ...")
        leg2 = checkpoint_cadence(scale, args.seed, shards,
                                  args.grid_mode, ckptdir)
        report["checkpoint_cadence"] = leg2
        for key, row in leg2.items():
            if not isinstance(row, dict):
                continue
            print(f"  {key}: {row['rounds_lost']} rounds lost, "
                  f"{row['checkpoints_written']} ckpts "
                  f"({row['checkpoint_write_s'] * 1e3:.1f} ms written)")

        print("serving kill-and-restore ...")
        leg3 = serving_kill_restore(scale, args.seed, shards, ckptdir)
        report["serving_kill_restore"] = leg3
        print(f"  {leg3['queries']} queries, {leg3['in_flight_at_kill']} "
              f"in flight at kill, {leg3['recovered_statuses']} RECOVERED,"
              f" restore {leg3['restore_s'] * 1e3:.1f} ms")

        print("streaming WAL replay ...")
        leg4 = streaming_wal_replay(scale, args.seed, shards, ckptdir)
        report["streaming_wal_replay"] = leg4
        print(f"  {leg4['wal_edges']} WAL edges, replay exact, restore "
              f"{leg4['restore_s'] * 1e3:.1f} ms")

    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2)
    print(f"wrote {args.out}")
    common.finish_report(report, obs_out=args.obs_out)


if __name__ == "__main__":
    main()


