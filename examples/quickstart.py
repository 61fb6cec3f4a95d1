"""Quickstart: Rhizomatic-RPVO graph processing in five minutes.

Builds a skewed synthetic graph, partitions it three ways ('simple
vertex', RPVO, Rhizomatic-RPVO), runs diffusive BFS / SSSP / PageRank on
the JAX engine, and prints the data-structure cost metrics that the
paper's technique improves.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.apps import bfs, pagerank, sssp
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators, reference
from repro.graph.graph import degree_stats
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

# 1. a highly skewed graph (RMAT, same generator family as the paper's R18)
g = generators.rmat(12, edge_factor=16, seed=0).with_random_weights(seed=0)
stats = degree_stats(g)
print(f"graph: V={stats['vertices']} E={stats['edges']} "
      f"max_in={stats['in']['max']} in_skew={stats['in_skew']:.1f}")

# 2. three layouts for the same graph
layouts = {
    "simple-vertex": PartitionConfig(num_shards=64, rpvo_max=1,
                                     ghost_alloc="home"),
    "rpvo": PartitionConfig(num_shards=64, rpvo_max=1,
                            ghost_alloc="balanced", local_edge_list_size=32),
    "rhizomatic-rpvo": PartitionConfig(num_shards=64, rpvo_max=16,
                                       ghost_alloc="balanced",
                                       local_edge_list_size=32),
}
parts = {}
for name, pc in layouts.items():
    part = build_partition(g, pc)
    parts[name] = part
    m = part.metrics
    print(f"{name:18s} E_max={m['E_max']:7d} (balance {m['edge_balance']:.2f}) "
          f"hot-inbox={m['max_inbox_per_slot']:6d} replicas=+{m['replicas_total']-g.n}")

# 3. run the three diffusive apps on the rhizomatic layout
root = int(np.argmax(g.out_degrees()))
part = parts["rhizomatic-rpvo"]

levels, st, _ = bfs(g, root, part=part)
assert (levels == reference.bfs_levels(g, root)).all()
print(f"BFS ok: {int(st.iterations)} rounds, "
      f"{int(st.messages)} actions, "
      f"{100 * int(st.work_actions) / max(int(st.messages), 1):.1f}% did work "
      f"(the rest pruned by their predicate)")

dist, _, _ = sssp(g, root, part=part)
ref = reference.sssp_dijkstra(g, root)
finite = np.isfinite(ref)
assert np.allclose(dist[finite], ref[finite], rtol=1e-5)
print("SSSP ok: matches Dijkstra oracle")

pr, _ = pagerank(g, iters=20, num_shards=64, rpvo_max=16)
assert np.allclose(pr, reference.pagerank(g, iters=20), rtol=1e-4, atol=1e-7)
print("PageRank ok: matches power-iteration oracle "
      "(rhizome-collapse = AND-gate all-reduce)")

# 4. query serving: many concurrent queries on ONE shared partition.
# A batch of mixed BFS/SSSP queries runs as lanes of a single fixpoint
# (the value table grows a query axis), and QueryServer continuously
# batches a request stream into lanes freed mid-flight — a short query
# never waits behind a long one.
from repro.apps import batched_queries
from repro.query import QueryServer

deg = np.argsort(-g.out_degrees())
queries = [("bfs", int(deg[0])), ("sssp", int(deg[1])),
           ("bfs", int(deg[2])), ("sssp", int(deg[3]))]
results, lane_stats, _ = batched_queries(g, queries, part=part)
assert (results[0] == reference.bfs_levels(g, int(deg[0]))).all()
print(f"lane batch ok: {len(queries)} queries, per-lane rounds="
      f"{np.asarray(lane_stats.rounds).tolist()}")

# the same batch on the compact *targeted* exchange (§Perf): only
# (target, distinct-slot) contributions travel — bit-identical results,
# strictly fewer exchanged entries per lane
from repro.core.engine import EngineConfig

res_c, stats_c, _ = batched_queries(
    g, queries, part=part, cfg=EngineConfig(exchange="compact"))
assert all((a == b).all() for a, b in zip(results, res_c))
dense_vol = int(np.asarray(lane_stats.exchanged).sum())
compact_vol = int(np.asarray(stats_c.exchanged).sum())
assert compact_vol < dense_vol
print(f"compact targeted exchange ok: bit-identical, "
      f"{dense_vol / compact_vol:.1f}x less exchange volume")

srv = QueryServer(part, n_lanes=2)   # 2 lanes << 5 queries: continuous batching
qids = [srv.submit(kind, root) for kind, root in queries]
qids.append(srv.submit("reachability", int(deg[4])))
served = srv.run()
assert (served[qids[0]].values == reference.bfs_levels(g, int(deg[0]))).all()
print(f"QueryServer ok: {len(served)} queries on 2 lanes in {srv.tick} "
      f"round ticks, occupancy {srv.occupancy():.2f}")

# 5. overload-safe serving (ISSUE 6): the same server behind a bounded
# admission queue with typed overload outcomes — a full queue rejects or
# sheds (never an exception), a priority-5 request preempts the
# lowest-priority running lane, an expired deadline evicts mid-flight
# with partial values, a zero round budget returns the initial values
# immediately, and repeat roots are served from the root-keyed cache.
from repro.query import QueryStatus, ServeConfig

srv = QueryServer(part, n_lanes=1, serve=ServeConfig(
    max_queue=2, overload_policy="reject", cache_size=8, cache_ttl_s=60.0))
q_slow = srv.submit("bfs", int(deg[0]))
q_wait = srv.submit("sssp", int(deg[1]))           # fills the queue...
q_over = srv.submit("bfs", int(deg[2]))            # ...typed rejection
srv.step()                                         # q_slow takes the lane
q_hot = srv.submit("bfs", int(deg[3]), priority=5)  # preempts q_slow
q_zero = srv.submit("sssp", int(deg[1]), max_rounds=0)  # initial values
served = srv.run()
assert served[q_over].status == QueryStatus.REJECTED
assert served[q_zero].status == QueryStatus.BUDGET_EXHAUSTED
assert served[q_zero].partial and served[q_hot].status == QueryStatus.OK
assert served[q_slow].preemptions == 1             # restarted, still right
assert (served[q_slow].values == reference.bfs_levels(g, int(deg[0]))).all()
q_again = srv.submit("bfs", int(deg[0]))           # repeat root: cache hit
assert srv.results[q_again].cached                 # resolved at submit
print(f"overload-safe serving ok: statuses "
      f"{sorted({r.status for r in served.values()})}, "
      f"{srv.counters['cache_hits']} cache hit, "
      f"{srv.counters['preemptions']} preemption — no exceptions")

# 6. sparsity-proportional execution (ISSUE 5): the worklist grid mode
# launches only the frontier-live kernel cells (grid_mode='auto' plans a
# sparse launch whenever the live fraction is thin), and delta-PageRank
# diffuses only residuals above a tolerance — the engine's diffusion
# pruning finally firing for the sum semiring.  smem_budget_bytes guards
# the scalar-prefetch tables on real-TPU-scale chunk counts.
from repro.apps import pagerank_delta

wl_cfg = EngineConfig(use_pallas=True, grid_mode="auto",
                      smem_budget_bytes=64 * 1024)
levels_wl, st_wl, _ = bfs(g, root, part=part, cfg=wl_cfg)
assert (levels_wl == levels).all() and int(st_wl.messages) == int(st.messages)
pr_delta, st_delta, _ = pagerank_delta(g, tol=1e-8, num_shards=64,
                                       rpvo_max=16, cfg=wl_cfg,
                                       max_rounds=200)
# dropped sub-tol residuals bound the rank error by O(tol/(1-d)) a round
assert np.allclose(pr_delta, reference.pagerank(g, iters=200),
                   rtol=1e-3, atol=1e-6)
print(f"worklist + delta-PageRank ok: BFS bit-identical under sparse "
      f"launches; delta-PR converged in {int(st_delta.iterations)} rounds, "
      f"{int(st_delta.pruned_actions)} diffusions pruned below tol")

# 7. the flight recorder (ISSUE 7): install a recorder, re-run the BFS
# fixpoint and a small QueryServer burst under it, and render the run
# summary.  Recording is off by default and costs nothing when off; on,
# every round's grid-cell columns are the same planner mirror the
# differential tests assert against the kernel's debug counters.
from repro import obs
from repro.obs import report

with obs.recording(meta={"demo": "quickstart"}) as recorder:
    levels_rec, st_rec, _ = bfs(g, root, part=part, cfg=wl_cfg)
    srv = QueryServer(part, n_lanes=2,
                      serve=ServeConfig(max_queue=8, cache_size=8))
    for r in (int(deg[0]), int(deg[1]), int(deg[2])):
        srv.submit("bfs", r)
    srv.run()
    srv.submit("bfs", int(deg[0]))                 # repeat root: cache hit
    srv.run()
assert (levels_rec == levels).all()                # recording changes nothing
assert sum(r.messages for r in recorder.rounds
           if r.run == "bfs") == int(st_rec.messages)
recorder.save("quickstart_obs_session.json")       # metrics + trace + rounds
print("-- flight recorder (python -m repro.obs.report) " + "-" * 22)
print(report.render(recorder.to_session()), end="")
print("obs ok: session saved to quickstart_obs_session.json "
      "(trace loads in Perfetto)")

# 8. device-resident fixpoints (ISSUE 8): grid_mode='device_worklist'
# compacts the frontier into the live-cell worklist ON DEVICE
# (cumsum-scatter over the frontier chunk bitmap), so the whole BFS
# fixpoint — sparse launches, convergence test and all — runs as ONE
# lax.while_loop dispatch with zero per-round host syncs instead of one
# dispatch + sync per round.  Same answer, bit for bit.
dev_cfg = EngineConfig(use_pallas=True, grid_mode="device_worklist")
reg = obs.registry()
before = sum(reg.counter("engine_dispatches_total")
             .snapshot_values().values())
levels_dev, st_dev, _ = bfs(g, root, part=part, cfg=dev_cfg)
dispatches = sum(reg.counter("engine_dispatches_total")
                 .snapshot_values().values()) - before
assert (levels_dev == levels).all() and dispatches == 1
print(f"device-resident fixpoint ok: {int(st_dev.iterations)} BFS rounds "
      f"in {dispatches} dispatch (host-driven pays "
      f"{int(st_dev.iterations)} dispatches + syncs)")

# the serving tick gets the same lever: tick_rounds=K advances every
# live lane K rounds per dispatch (lanes carrying round budgets or
# deadlines drop back to K=1 so their policing stays per-round exact)
srv = QueryServer(part, n_lanes=2, cfg=dev_cfg, tick_rounds=4)
for kind, r in queries:
    srv.submit(kind, r)
served_dev = srv.run()
assert (served_dev[0].values == reference.bfs_levels(g, int(deg[0]))).all()
print(f"windowed serving ok: {len(served_dev)} queries, "
      f"{srv.rounds_driven} pool rounds in {srv.tick} ticks "
      f"(~{srv.rounds_driven / max(srv.tick, 1):.1f} rounds/dispatch)")

# 9. streaming graphs (ISSUE 9): mutate the graph WHILE it serves.
# StreamingGraph buffers edge insert/delete batches; commit() splices
# only the affected shard rows of every live partition (counter-hashed
# placement makes the splice field-identical to a from-scratch build),
# warm-starts tracked fixpoints at just the affected region, splits a
# vertex into a new rhizome replica online when streamed in-degree
# crosses the pinned Eq. 1 cutoff — and swaps a bound QueryServer onto
# the new partition between ticks, firing its cache-invalidation hooks.
from repro.core.streaming import StreamingGraph
from repro.query import QueryServer, ServeConfig

gs = generators.rmat(8, edge_factor=8, seed=0).with_random_weights(seed=0)
stream = StreamingGraph(gs, PartitionConfig(num_shards=8, rpvo_max=4))
sroot = int(np.argmax(gs.out_degrees()))
stream.track("bfs", sroot)                  # maintained incrementally
srv = QueryServer(stream.view("base").part, n_lanes=2,
                  serve=ServeConfig(cache_size=16))
stream.bind_server(srv)                     # mutations apply between ticks

qid = srv.submit("bfs", sroot)
srv.run()                                   # cold serve, result cached
rng = np.random.default_rng(0)
stream.insert_edges(rng.integers(0, gs.n, 32).astype(np.int32),
                    rng.integers(0, gs.n, 32).astype(np.int32))
stream.delete_edges(stream.g.src[:4], stream.g.dst[:4])
info = stream.commit()                      # splice + maintain + notify
ms = info.maint[("bfs", sroot)]
sp = info.splices["base"]
assert srv.counters["cache_invalidations"] >= 1   # stale entry dropped
qid2 = srv.submit("bfs", sroot)
srv.run()                                   # recomputed on the new graph
assert (srv.results[qid2].values
        == reference.bfs_levels(stream.g, sroot)).all()
slv = stream.values("bfs", sroot)
lvl = np.full(gs.n, np.iinfo(np.int32).max, np.int64)
lvl[np.isfinite(slv)] = slv[np.isfinite(slv)].astype(np.int64)
assert (lvl == reference.bfs_levels(stream.g, sroot)).all()
print(f"streaming ok: {info.inserted}+{info.deleted} edge mutations, "
      f"{sp.shards_rebuilt}/{sp.shards_total} shard rows respliced, "
      f"+{info.replicas_added} rhizome replicas, incremental BFS "
      f"re-lifted {ms.invalidated} vertices in {ms.messages} messages "
      f"({ms.rounds} rounds) — server cache invalidated, fresh answer "
      f"served")

# 10. crash-safe fixpoints (ISSUE 10): kill a shard mid-run, restore
# from the last checkpoint, land on the exact same answer.  The
# resilient driver checkpoints {value tables, frontier, counters} at
# round boundaries, detects the death through the heartbeat window (a
# crc scrub and a message-count mirror catch corruptions and lost
# inboxes the same way), re-dispatches from the checkpoint, and — since
# the accounting rides inside the checkpoint tree — finishes with
# totals EQUAL to a run that never crashed.
import tempfile

from repro.checkpoint.manager import CheckpointManager
from repro.core import actions, engine
from repro.core.resilient import StackedTask, run_resilient
from repro.runtime.chaos import ChaosEvent, ChaosPlan

kcfg = engine.EngineConfig(checkpoint_every=2)
kinit = engine.init_values(part, actions.SSSP, {root: 0.0})
clean, clean_st = engine.run_stacked(actions.SSSP, part, kinit,
                                     engine.EngineConfig())
chaos = ChaosPlan(events=(
    ChaosEvent(round=3, kind="kill_shard", shard=1),))
with tempfile.TemporaryDirectory() as ckdir:
    rval, rst, rep = run_resilient(
        StackedTask(actions.SSSP, part, kinit, kcfg), chaos=chaos,
        manager=CheckpointManager(ckdir))
assert rep.status == "recovered" and len(rep.faults) == 1
assert (np.asarray(rval) == np.asarray(clean)).all()       # bit-equal
assert int(rst.messages) == int(clean_st.messages)         # exact totals
assert int(rst.iterations) == int(clean_st.iterations)
print(f"crash-safe fixpoint ok: shard killed at round 3, detected by "
      f"the heartbeat window, restored from the last checkpoint "
      f"({rep.checkpoints_written} written, {rep.rounds_lost} rounds "
      f"replayed) — values bit-equal, {int(rst.messages)} messages "
      f"exactly equal the uninterrupted run")
