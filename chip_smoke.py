#!/usr/bin/env python3
"""Chip smoke: drive the graph engine's main path once on a TPU and check
every answer against an independent reference.

    python chip_smoke.py              # one chip: BFS, SSSP, PageRank, server
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

The graph is the paper's R-series RMAT (PaRMAT a=0.45, b=0.25, c=0.15) at
scale 20 — 1,048,576 vertices, about 16.8M directed edges — made from
``--seed``.  One chip runs it partitioned into 16 shards with rhizomes
(``rpvo_max=4``) stacked on the device; every phase uses the fused Pallas
kernel with host-planned worklist launches
(``EngineConfig(use_pallas=True, grid_mode="worklist")``):

* ``bfs`` / ``sssp`` from the vertex with the highest out-degree, checked
  against scipy's csgraph shortest paths (levels exactly, distances to a
  relative 1e-6 — integer weights sum exactly in f32);
* ``pagerank`` for ``PR_ITERS`` counted iterations, checked against
  ``graph/reference.py`` to a relative 1e-4 (f32 against f64);
* a ``QueryServer(n_lanes=16)`` burst of 8 mixed BFS / SSSP / PPR
  requests on one partition, each checked against scipy (BFS exactly,
  SSSP to a relative 1e-5) or a numpy power iteration (PPR to an absolute
  ``PPR_ATOL``: delta rounds drop residuals below ``PPR_TOL``).

``--chips 4`` runs only the sharded path over a 2x2 mesh of the first four
devices: a 4-shard partition, the BFS fixpoint under ``shard_map`` (its
traced loop compacts the worklist on device) and a sharded ``QueryServer``
burst, checked against the same references, with the value table's
shards placed on four distinct devices.

Earlier lines report, per phase, the seconds to compile the phase's round
program, the wall seconds of the first call (host-planned fixpoints
compile once per worklist length bucket inside it) and, where it is
cheap enough to repeat, of a second call (smoke timings on one machine,
not benchmarks), the largest error against the reference, and whether
the compiled program holds the Pallas kernel (``tpu_custom_call``).
The last line is one JSON object naming the device.  Without a TPU — or
without the repo's ``src/`` beside this file — the script exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

SCALE, EDGE_FACTOR = 20, 16
SHARDS, RPVO_MAX = 16, 4
PR_ITERS, DAMPING = 5, 0.85
PPR_TOL, PPR_ATOL = 1e-8, 1e-5
N_LANES = 16
UNREACHED = (1 << 31) - 1      # the engine's BFS level for unreached


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------- references
# scipy / numpy only: nothing here touches the engine

def csgraph(g, weights):
    import scipy.sparse as sp
    return sp.csr_matrix((weights, (g.src, g.dst)), shape=(g.n, g.n))


def ref_bfs(g, root):
    from scipy.sparse.csgraph import shortest_path
    import numpy as np
    d = shortest_path(csgraph(g, np.ones(g.src.shape[0])), unweighted=True,
                      indices=root)
    return d


def ref_sssp(g, weights, root):
    from scipy.sparse.csgraph import dijkstra
    import numpy as np
    return dijkstra(csgraph(g, np.asarray(weights, np.float64)),
                    indices=root)


def ref_ppr(g, seed, damping, tol=1e-11, max_iters=5000):
    import numpy as np
    out = g.out_degrees().astype(np.float64)
    x = np.zeros(g.n)
    x[seed] = 1.0
    base = np.zeros(g.n)
    base[seed] = 1.0 - damping
    for _ in range(max_iters):
        c = np.where(out > 0, x / np.maximum(out, 1), 0.0)
        new = base + damping * np.bincount(g.dst, weights=c[g.src],
                                           minlength=g.n)
        done = np.abs(new - x).max() <= tol
        x = new
        if done:
            break
    return x


def level_errors(got, want_dist):
    """Mismatched BFS levels (unreachable: the engine's sentinel vs inf)."""
    import numpy as np
    want = np.where(np.isfinite(want_dist), want_dist, -1).astype(np.int64)
    want[~np.isfinite(want_dist)] = UNREACHED
    return int((np.asarray(got) != want).sum())


def rel_error(got, want):
    """Largest |got - want| / |want| over reachable vertices; inf when the
    two disagree on reachability."""
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    fin = np.isfinite(want)
    if (np.isfinite(got) != fin).any():
        return float("inf")
    if not fin.any():
        return 0.0
    return float((np.abs(got[fin] - want[fin])
                  / np.maximum(np.abs(want[fin]), 1e-30)).max())


# -------------------------------------------------------------------- phases

def holds_kernel(fn, *args) -> tuple[bool, float]:
    """Compile ``fn`` for ``args`` and report whether the compiled program
    holds the Pallas kernel, with the compile seconds."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return "tpu_custom_call" in compiled.as_text(), \
        time.perf_counter() - t0


def report(name, ok, err, tcc, compile_s, first_s, second_s=None,
           extra=""):
    second = ("" if second_s is None
              else f" second_call_s={second_s:.3f}")
    log(f"phase {name}: {'ok' if ok else 'FAILED'} max_err={err!r} "
        f"tpu_custom_call={'yes' if tcc else 'no'} "
        f"compile_s={compile_s:.3f} first_call_s={first_s:.3f}"
        f"{second}{extra}")
    return ok and tcc


def fixpoint_round_fn(sem, part, cfg, root):
    """The round program the host-driven fixpoint loop launches, with
    its first-round inputs and worklist plan."""
    import jax.numpy as jnp
    import numpy as np
    from repro import exchange
    from repro.core import engine
    arrays = engine.DeviceArrays.from_partition(part)
    val = jnp.asarray(engine.init_values(part, sem, {root: 0.0}))
    chg = sem.improved(val, jnp.full_like(val, sem.identity)) \
        & arrays.slot_valid
    wl = engine.plan_round_worklist(engine.launch_planner(part, cfg), cfg,
                                    np.asarray(chg).reshape(-1))

    def fn(ar, v, c, w):
        return exchange.fixpoint_round_stacked(sem, ar, cfg, part.S,
                                               part.R_max, v, c, worklist=w)
    return fn, arrays, val, chg, wl


def one_chip(args) -> bool:
    import jax.numpy as jnp
    import numpy as np
    from repro import apps, exchange
    from repro.core import actions, engine
    from repro.core.partition import PartitionConfig, build_partition
    from repro.graph import reference
    from repro.query import QueryServer

    cfg = engine.EngineConfig(use_pallas=True, grid_mode="worklist")
    pcfg = PartitionConfig(num_shards=SHARDS, rpvo_max=RPVO_MAX)
    (g, gw, g_pr), gen_s = timed(lambda: _graphs(args.seed))
    root = int(np.argmax(g.out_degrees()))
    part, part_s = timed(lambda: build_partition(gw, pcfg))
    part_pr, part_pr_s = timed(lambda: build_partition(g_pr, pcfg))
    log(f"graph rmat scale={SCALE} n={g.n} edges={g.num_edges} root={root} "
        f"shards={SHARDS} rpvo_max={RPVO_MAX} slots={part.S * part.R_max} "
        f"gen_s={gen_s:.3f} partition_s={part_s:.3f} "
        f"partition_pr_s={part_pr_s:.3f}")
    all_ok = True

    # BFS / SSSP: stacked fixpoints, host-planned worklist rounds
    for name, app, sem in (("bfs", apps.bfs, actions.BFS),
                           ("sssp", apps.sssp, actions.SSSP)):
        tcc, compile_s = holds_kernel(*fixpoint_round_fn(sem, part, cfg,
                                                         root))
        (out, stats, _), first_s = timed(
            lambda: app(g, root, part=part, cfg=cfg))
        if name == "bfs":
            err = level_errors(out, ref_bfs(g, root))
            ok = err == 0
        else:
            err = rel_error(out, ref_sssp(g, gw.weight, root))
            ok = err <= 1e-6
        all_ok &= report(name, ok, err, tcc, compile_s, first_s,
                         extra=f" rounds={int(stats.iterations)} "
                         f"messages={int(stats.messages)}")

    # PageRank: counted rounds, one worklist planned for the constant
    # all-slots frontier
    arrays = engine.DeviceArrays.from_partition(part_pr)
    slot_valid = arrays.slot_valid
    wl = engine.plan_round_worklist(engine.launch_planner(part_pr, cfg),
                                    cfg, np.asarray(slot_valid).reshape(-1))
    base = (1.0 - DAMPING) / part_pr.n
    tcc, compile_s = holds_kernel(
        lambda ar, v, w: exchange.pagerank_round_stacked(
            actions.PAGERANK, ar, cfg, part_pr.S, part_pr.R_max, base,
            DAMPING, v, ar.slot_valid, worklist=w),
        arrays, jnp.where(slot_valid, 1.0 / part_pr.n, 0.0), wl)
    (pr, _), first_s = timed(lambda: apps.pagerank(
        g, DAMPING, PR_ITERS, part=part_pr, cfg=cfg))
    (pr, _), second_s = timed(lambda: apps.pagerank(
        g, DAMPING, PR_ITERS, part=part_pr, cfg=cfg))
    want = reference.pagerank(g, DAMPING, PR_ITERS)
    err = rel_error(pr, want)
    all_ok &= report("pagerank", err <= 1e-4, err, tcc, compile_s, first_s,
                     second_s, extra=f" iters={PR_ITERS}")

    # QueryServer: 8 mixed requests on the PageRank-weighted partition
    # (BFS ignores weights, SSSP reads them as distances, PPR as 1/outdeg)
    srv = QueryServer(part_pr, n_lanes=N_LANES, cfg=cfg)
    deg = np.argsort(-g.out_degrees())
    burst = [("bfs", int(deg[0])), ("sssp", int(deg[1])),
             ("ppr", int(deg[2])), ("bfs", int(deg[3])),
             ("sssp", int(deg[4])), ("ppr", int(deg[5])),
             ("bfs", int(deg[6])), ("sssp", int(deg[7]))]
    mp, pp = srv.min_pool, srv.ppr_pool
    tcc_min, c1 = holds_kernel(mp._round, mp.val, mp.chg,
                               jnp.asarray(mp.unitw), mp._plan())
    tcc_ppr, c2 = holds_kernel(pp._round, pp.rank, pp.delta,
                               jnp.asarray(pp.damping), jnp.asarray(pp.tol),
                               pp._plan())

    def serve():
        qids = [srv.submit(kind, src, tol=PPR_TOL) for kind, src in burst]
        res = srv.run()
        return [res[q] for q in qids]

    results, first_s = timed(serve)
    results, second_s = timed(serve)
    w_pr = np.asarray(g_pr.weight)
    n_good, worst = 0, 0.0
    for (kind, src), r in zip(burst, results):
        if kind == "bfs":
            e = level_errors(r.values, ref_bfs(g, src))
            good = e == 0
        elif kind == "sssp":
            e = rel_error(r.values, ref_sssp(g, w_pr, src))
            good = e <= 1e-5
        else:
            e = float(np.abs(r.values - ref_ppr(g, src, DAMPING)).max())
            good = e <= PPR_ATOL
        good &= r.status == "ok"
        n_good += bool(good)
        worst = max(worst, float(e))
        log(f"  request {kind}({src}): status={r.status} rounds={r.rounds} "
            f"err={e!r} {'ok' if good else 'WRONG'}")
    all_ok &= report("server", n_good == len(burst), worst,
                     tcc_min and tcc_ppr, c1 + c2, first_s, second_s,
                     extra=f" correct={n_good}/{len(burst)} lanes={N_LANES}")
    return all_ok


def four_chips(args) -> bool:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    import jax.numpy as jnp
    from repro.core import actions, engine
    from repro.core.partition import PartitionConfig, build_partition
    from repro.query import QueryServer

    cfg = engine.EngineConfig(use_pallas=True, grid_mode="worklist")
    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices).reshape(2, 2), ("data", "model"))
    (g, gw, _), gen_s = timed(lambda: _graphs(args.seed))
    root = int(np.argmax(g.out_degrees()))
    part, part_s = timed(lambda: build_partition(
        gw, PartitionConfig(num_shards=4, rpvo_max=RPVO_MAX)))
    log(f"graph rmat scale={SCALE} n={g.n} edges={g.num_edges} root={root} "
        f"shards=4 mesh=2x2 gen_s={gen_s:.3f} partition_s={part_s:.3f}")
    all_ok = True

    # the BFS fixpoint under shard_map — the path apps.bfs(mesh=) takes;
    # its traced loop routes the worklist grid to device_worklist
    init = engine.init_values(part, actions.BFS, {root: 0.0})
    fn, sharding = engine.make_sharded_fn(actions.BFS, part.S, part.R_max,
                                          mesh, ("data", "model"), cfg)
    arrays = jax.tree.map(lambda x: jax.device_put(x, sharding),
                          engine.DeviceArrays.from_partition(part))
    val0 = jax.device_put(np.asarray(init), sharding)
    t0 = time.perf_counter()
    compiled = fn.lower(arrays, val0).compile()
    compile_s = time.perf_counter() - t0
    tcc = "tpu_custom_call" in compiled.as_text()
    (val, stats), first_s = timed(lambda: jax.block_until_ready(
        compiled(arrays, val0)))
    (val, stats), second_s = timed(lambda: jax.block_until_ready(
        compiled(arrays, val0)))
    shard_devices = {s.device for s in val.addressable_shards}
    lv = engine.vertex_values(part, val)
    levels = np.where(np.isfinite(lv), lv, 0).astype(np.int64)
    levels[~np.isfinite(lv)] = UNREACHED
    err = level_errors(levels, ref_bfs(g, root))
    ok = err == 0 and len(shard_devices) == 4
    all_ok &= report("bfs_sharded", ok, err, tcc, compile_s, first_s,
                     second_s, extra=f" rounds={int(stats.iterations[0])} "
                     f"value_table_devices={len(shard_devices)}")

    srv = QueryServer(part, n_lanes=8, cfg=cfg, mesh=mesh)
    mp = srv.min_pool
    tcc, compile_s = holds_kernel(mp._round, mp._arrays, mp.val, mp.chg,
                                  jnp.asarray(mp.unitw))
    deg = np.argsort(-g.out_degrees())
    burst = [("bfs", int(deg[0])), ("sssp", int(deg[1])),
             ("bfs", int(deg[2])), ("sssp", int(deg[3]))]

    def serve():
        qids = [srv.submit(kind, src) for kind, src in burst]
        res = srv.run()
        return [res[q] for q in qids]

    results, first_s = timed(serve)
    results, second_s = timed(serve)
    table_devices = {s.device for s in srv.min_pool.val.addressable_shards}
    n_good, worst = 0, 0.0
    for (kind, src), r in zip(burst, results):
        if kind == "bfs":
            e = level_errors(r.values, ref_bfs(g, src))
            good = e == 0
        else:
            e = rel_error(r.values, ref_sssp(g, gw.weight, src))
            good = e <= 1e-6
        good &= r.status == "ok"
        n_good += bool(good)
        worst = max(worst, float(e))
        log(f"  request {kind}({src}): status={r.status} rounds={r.rounds} "
            f"err={e!r} {'ok' if good else 'WRONG'}")
    all_ok &= report("server_sharded",
                     n_good == len(burst) and len(table_devices) == 4,
                     worst, tcc, compile_s, first_s, second_s,
                     extra=f" correct={n_good}/{len(burst)} "
                     f"value_table_devices={len(table_devices)}")
    for d in devices:
        stats_d = d.memory_stats() or {}
        log(f"device {d.id} bytes_in_use={stats_d.get('bytes_in_use')}")
    return all_ok


def _graphs(seed):
    from repro.apps.pagerank import _pr_graph
    from repro.graph import generators
    g = generators.rmat(SCALE, edge_factor=EDGE_FACTOR, seed=seed)
    return g, g.with_random_weights(seed=seed), _pr_graph(g)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: the sharded phase only")
    ap.add_argument("--seed", type=int, default=1,
                    help="RNG seed for the graph and its weights")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"needs a TPU, but JAX found platform {platform!r} "
             f"({len(devices)} device(s)); no phase was run")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPU devices; JAX "
             f"found {len(devices)}")
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repro package is not beside this script ({e})")
    cache = enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    kind = devices[0].device_kind
    log(f"device platform={platform} kind={kind} count={len(devices)} "
        f"compile_cache={cache} cache_entries_at_start={warm}")

    ok = one_chip(args) if args.chips == 1 else four_chips(args)
    if not ok:
        fail("a phase failed (see the phase lines above)")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}),
        flush=True)


if __name__ == "__main__":
    main()
