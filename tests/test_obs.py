"""Flight recorder (ISSUE 7): metrics/trace units, obs-off parity, and
the differential round-record harness.

The two acceptance bars pinned here:

* **Obs off is free** — with no recorder installed, ``run_stacked``
  dispatches exactly as before (traced ``while_loop``, no host loop),
  and the traced round function's jaxpr is byte-identical whether or
  not a recorder exists in the process.
* **Obs on is exact** — every recorded ``RoundRecord``'s grid-cell
  columns equal a freshly recomputed ``fused_grid_cells`` host mirror
  AND the fused kernel's ``with_debug`` executed-cell counter on that
  round's actual frontier, across dense/worklist/auto grids.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import exchange, obs
from repro.core import actions, engine
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators
from repro.kernels.fused_relax_reduce import (
    fused_grid_cells, fused_relax_reduce_pallas,
)
from repro.obs import report
from repro.serve.admission import ResultCache

# --------------------------------------------------------------------------
# metrics registry units
# --------------------------------------------------------------------------

def test_counter_gauge_labels_snapshot_delta():
    reg = obs.MetricsRegistry()
    c = reg.counter("msgs_total", "messages")
    c.labels(run="bfs").inc(5)
    c.labels(run="bfs").inc(2)
    c.labels(run="sssp").inc()
    g = reg.gauge("frontier", "live slots")
    g.labels(run="bfs").set(42)

    before = reg.snapshot()
    assert before["msgs_total"]["series"][(("run", "bfs"),)] == 7
    assert before["msgs_total"]["series"][(("run", "sssp"),)] == 1
    assert before["frontier"]["series"][(("run", "bfs"),)] == 42

    c.labels(run="bfs").inc(3)
    g.labels(run="bfs").set(10)
    d = reg.delta(before)
    # counters subtract; gauges report current level
    assert d["msgs_total"]["series"][(("run", "bfs"),)] == 3
    assert d["frontier"]["series"][(("run", "bfs"),)] == 10

    with pytest.raises(ValueError):
        reg.gauge("msgs_total")     # kind collision on a name


def test_histogram_buckets_and_prometheus_exposition():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    text = reg.render_prometheus()
    assert "# TYPE lat_seconds histogram" in text
    # cumulative bucket counts: <=0.1:1, <=1:3, <=10:4, +Inf:5
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 3' in text
    assert 'lat_seconds_bucket{le="10"} 4' in text
    assert 'lat_seconds_bucket{le="+Inf"} 5' in text
    assert "lat_seconds_count 5" in text
    assert "lat_seconds_sum 56.05" in text

    reg.counter("c_total", "c").labels(app="a b").inc()
    text = reg.render_prometheus()
    assert 'c_total{app="a b"} 1' in text


# --------------------------------------------------------------------------
# tracer / chrome schema
# --------------------------------------------------------------------------

def test_trace_chrome_schema_and_deterministic_clock():
    t = [0.0]
    tracer = obs.Tracer(clock=lambda: t[0])
    with tracer.span("round", track="engine", round=1):
        t[0] = 0.25
    tracer.instant("preempt", track="requests", qid=3)
    tracer.counter("server", {"queue_depth": 4})
    tracer.complete("queued", track="requests", start=0.1, end=0.2, qid=3)

    doc = tracer.to_chrome()
    blob = json.loads(json.dumps(doc))          # JSON round-trips
    evs = blob["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"thread_name", "round", "preempt", "server", "queued"} <= names
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        assert e["ph"] in ("X", "i", "C", "M")
        if e["ph"] != "M":
            assert isinstance(e["ts"], (int, float))
    span = next(e for e in evs if e["name"] == "round")
    assert span["ts"] == 0.0 and span["dur"] == 0.25e6   # exact: fake clock
    q = next(e for e in evs if e["name"] == "queued")
    assert q["ts"] == pytest.approx(0.1e6) and q["dur"] == pytest.approx(0.1e6)
    # distinct tracks land on distinct tids, named by metadata
    tids = {e["args"]["name"]: e["tid"] for e in evs if e["ph"] == "M"}
    assert len(set(tids.values())) == len(tids) >= 3


def test_recording_installs_and_restores():
    assert obs.get_recorder() is None
    with obs.recording() as outer:
        assert obs.get_recorder() is outer
        with obs.recording() as inner:
            assert obs.get_recorder() is inner
        assert obs.get_recorder() is outer
    assert obs.get_recorder() is None


# --------------------------------------------------------------------------
# obs-off parity: disabled must be trace-identical to today's engine
# --------------------------------------------------------------------------

def _small_case(seed=3):
    g = generators.rmat(7, edge_factor=5, seed=seed) \
        .with_random_weights(seed=seed)
    part = build_partition(g, PartitionConfig(num_shards=4, rpvo_max=2))
    root = int(np.argmax(g.out_degrees()))
    return g, part, root


def test_obs_off_dispatch_unchanged(monkeypatch):
    """No recorder -> the dense-grid fixpoint takes the traced
    while_loop, never the host-driven loop; a recorder reroutes it."""
    _, part, root = _small_case()
    cfg = engine.EngineConfig(use_pallas=True)
    init = engine.init_values(part, actions.BFS, {root: 0.0})

    calls = []
    real = engine._run_stacked_hostloop
    monkeypatch.setattr(
        engine, "_run_stacked_hostloop",
        lambda *a, **k: calls.append(1) or real(*a, **k))

    val_off, st_off = engine.run_stacked(actions.BFS, part, init, cfg)
    assert calls == []                      # traced path, as pre-obs
    with obs.recording():
        val_on, st_on = engine.run_stacked(actions.BFS, part, init, cfg)
    assert calls == [1]                     # recorder -> host loop
    np.testing.assert_array_equal(np.asarray(val_on), np.asarray(val_off))
    assert int(st_on.messages) == int(st_off.messages)
    assert int(st_on.iterations) == int(st_off.iterations)
    assert int(st_on.pruned_actions) == int(st_off.pruned_actions)


def test_obs_off_round_jaxpr_identical():
    """The traced round function's jaxpr is byte-identical with and
    without a recorder in the process — recording never touches jit."""
    _, part, root = _small_case()
    cfg = engine.EngineConfig(use_pallas=True)
    arrays = engine.DeviceArrays.from_partition(part)
    init = jnp.asarray(engine.init_values(part, actions.BFS, {root: 0.0}))
    chg0 = jnp.zeros_like(init, bool).at[0, 0].set(True)

    def jx():
        fn = lambda v, c: exchange.fixpoint_round_stacked(  # noqa: E731
            actions.BFS, arrays, cfg, part.S, part.R_max, v, c)
        return str(jax.make_jaxpr(fn)(init, chg0))

    off = jx()
    with obs.recording():
        on = jx()
    assert on == off


# --------------------------------------------------------------------------
# the differential harness: RoundRecord == host mirror == kernel debug
# --------------------------------------------------------------------------

def _kernel_args(part, gval_flat, gchg):
    return (jnp.asarray(gval_flat), jnp.asarray(gchg),
            jnp.asarray(part.edge_src_root_flat.reshape(-1)),
            jnp.asarray(part.edge_w.reshape(-1), jnp.float32),
            jnp.asarray(part.edge_mask.reshape(-1)),
            jnp.asarray(part.edge_dst_flat.reshape(-1)))


def _assert_record_exact(part, cfg, rec, runs):
    """Every kept round: the record's counters equal (a) a freshly
    recomputed fused_grid_cells mirror, (b) the kernel's with_debug
    counters on that frontier, (c) the per-shard message mirror."""
    planner = engine.launch_planner(part, cfg)
    total = part.S * part.R_max
    rng = np.random.default_rng(0)
    gval = rng.uniform(0.0, 5.0, total).astype(np.float32)
    checked = 0
    assert len(rec.rounds) == len(rec.frontiers) > 0
    for r, gchg in zip(rec.rounds, rec.frontiers):
        if r.run not in runs:
            continue
        checked += 1
        sem = {"bfs": actions.BFS, "sssp": actions.SSSP,
               "pagerank": actions.PAGERANK}[r.run.split("_")[0]]
        assert r.frontier == int(gchg.sum())
        # (c) shard mirror: partitions messages exactly
        shard = exchange.shard_message_mirror(
            part.edge_mask, part.edge_src_root_flat, gchg)
        assert r.shard_messages == [int(x) for x in shard]
        assert sum(r.shard_messages) == r.messages
        assert r.path == "fused"
        if r.grid == "worklist":
            wl, info = engine.plan_round_worklist(
                planner, cfg, gchg, with_info=True)
            assert wl is not None
            # (a) the planner mirror of the replanned launch
            assert (r.cells, r.launched) == (info.cells, info.launched)
            mirror = fused_grid_cells(
                np.asarray(part.edge_dst_flat), np.asarray(part.edge_mask),
                np.asarray(part.edge_src_root_flat), gchg, total,
                grid_mode="worklist")
            assert r.cells == mirror["wl_cells"]
            # (b) kernel-side counters
            _, dbg = fused_relax_reduce_pallas(
                *_kernel_args(part, gval, gchg), total, sem.relax_kind,
                sem.segment, interpret=True, worklist=wl, with_debug=True)
        else:
            mirror = fused_grid_cells(
                np.asarray(part.edge_dst_flat), np.asarray(part.edge_mask),
                np.asarray(part.edge_src_root_flat), gchg, total)
            assert r.cells == mirror["fused_live"]
            assert r.launched == mirror["total_fused"]
            _, dbg = fused_relax_reduce_pallas(
                *_kernel_args(part, gval, gchg), total, sem.relax_kind,
                sem.segment, interpret=True, with_debug=True)
        assert int(dbg[0]) == r.cells, (r.run, r.round)
    assert checked > 0


@pytest.mark.parametrize("grid_mode", ["dense", "worklist", "auto"])
@pytest.mark.parametrize("seed", [3, 11])
def test_round_records_equal_mirror_and_kernel_debug(grid_mode, seed):
    _, part, root = _small_case(seed)
    cfg = engine.EngineConfig(use_pallas=True, grid_mode=grid_mode)
    for sem in (actions.BFS, actions.SSSP):
        with obs.recording(keep_frontiers=True) as rec:
            init = engine.init_values(part, sem, {root: 0.0})
            engine.run_stacked(sem, part, init, cfg)
        _assert_record_exact(part, cfg, rec, {sem.name})


@pytest.mark.parametrize("grid_mode", ["dense", "auto"])
def test_pagerank_delta_records_equal_mirror(grid_mode):
    g = generators.rmat(7, edge_factor=5, seed=3)
    from repro.apps.pagerank import _pr_graph
    part = build_partition(_pr_graph(g),
                           PartitionConfig(num_shards=4, rpvo_max=2))
    cfg = engine.EngineConfig(use_pallas=True, grid_mode=grid_mode)
    with obs.recording(keep_frontiers=True) as rec:
        engine.run_pagerank_delta(part, tol=3e-5, cfg=cfg, max_rounds=8)
    _assert_record_exact(part, cfg, rec, {"pagerank_delta"})


# --------------------------------------------------------------------------
# ISSUE 8: per-WINDOW records under the device-resident fixpoint loop
# --------------------------------------------------------------------------

def _window_slices(host_rounds, k):
    """Host rounds grouped into the K-round windows the device loop
    dispatches: [0:k], [k:2k], ..."""
    return [host_rounds[i:i + k] for i in range(0, len(host_rounds), k)]


@pytest.mark.parametrize("k", [1, 2, 8])
def test_device_window_records_sum_to_host_rounds(k):
    """grid_mode='device_worklist' + recorder => one RoundRecord per
    K-round dispatch window; every additive column must sum to the
    host-driven per-round records' totals, window by window, and the
    planner mirror is recomputed post-hoc from the returned frontier
    trajectory (the record's cells column)."""
    _, part, root = _small_case()
    cfg_h = engine.EngineConfig(use_pallas=True)
    cfg_dev = engine.EngineConfig(use_pallas=True,
                                  grid_mode="device_worklist",
                                  device_window=k)
    for sem in (actions.BFS, actions.SSSP):
        init = engine.init_values(part, sem, {root: 0.0})
        with obs.recording(keep_frontiers=True) as rec_h:
            val_h, st_h = engine.run_stacked(sem, part, init, cfg_h)
        with obs.recording(keep_frontiers=True) as rec_d:
            val_d, st_d = engine.run_stacked(sem, part, init, cfg_dev)
        np.testing.assert_array_equal(np.asarray(val_d),
                                      np.asarray(val_h))
        host = [r for r in rec_h.rounds if r.run == sem.name]
        dev = [r for r in rec_d.rounds if r.run == sem.name]
        assert all(r.window == 0 for r in host)      # per-round records
        assert [r.window for r in dev] == \
            list(range(1, len(dev) + 1))             # 1-based windows
        assert all(r.grid == "device_worklist" for r in dev)
        wins = _window_slices(host, k)
        assert len(dev) == len(wins)
        for dr, hw in zip(dev, wins):
            # the window's cumulative round count and entering frontier
            assert dr.round == hw[-1].round
            assert dr.frontier == hw[0].frontier
            for col in ("messages", "work", "pruned", "cells"):
                assert getattr(dr, col) == \
                    sum(getattr(r, col) for r in hw), (sem.name, col)
            assert dr.shard_messages == [
                sum(col) for col in zip(*(r.shard_messages for r in hw))]
        # grand totals == RunStats == host totals
        assert sum(r.messages for r in dev) == int(st_d.messages) \
            == int(st_h.messages)
        assert sum(r.work for r in dev) == int(st_d.work_actions)
        # frontier bitmaps: window w enters on the host frontier of its
        # first round (the post-hoc mirror's recompute anchor)
        for gdev, hw in zip(rec_d.frontiers, wins):
            np.testing.assert_array_equal(gdev, rec_h.frontiers[
                rec_h.rounds.index(hw[0])])


def test_device_window_pagerank_delta_sums():
    g = generators.rmat(7, edge_factor=5, seed=3)
    from repro.apps.pagerank import _pr_graph
    part = build_partition(_pr_graph(g),
                           PartitionConfig(num_shards=4, rpvo_max=2))
    cfg_h = engine.EngineConfig(use_pallas=True)
    cfg_dev = engine.EngineConfig(use_pallas=True,
                                  grid_mode="device_worklist",
                                  device_window=3)
    with obs.recording(keep_frontiers=True) as rec_h:
        rank_h, st_h = engine.run_pagerank_delta(part, tol=3e-5,
                                                 cfg=cfg_h, max_rounds=8)
    with obs.recording(keep_frontiers=True) as rec_d:
        rank_d, st_d = engine.run_pagerank_delta(part, tol=3e-5,
                                                 cfg=cfg_dev,
                                                 max_rounds=8)
    # sum semiring: equal up to the traced loop's reassociation (min
    # semirings are bit-identical — see the fixpoint window test above)
    np.testing.assert_allclose(np.asarray(rank_d), np.asarray(rank_h),
                               rtol=1e-6, atol=1e-9)
    host = [r for r in rec_h.rounds if r.run == "pagerank_delta"]
    dev = [r for r in rec_d.rounds if r.run == "pagerank_delta"]
    assert len(dev) == -(-len(host) // 3)
    for dr, hw in zip(dev, _window_slices(host, 3)):
        for col in ("messages", "work", "pruned", "cells"):
            assert getattr(dr, col) == sum(getattr(r, col) for r in hw)
    assert sum(r.messages for r in dev) == int(st_h.messages)
    assert int(st_d.messages) == int(st_h.messages)


def test_window_field_serializes():
    _, part, root = _small_case()
    cfg = engine.EngineConfig(use_pallas=True,
                              grid_mode="device_worklist",
                              device_window=2)
    with obs.recording() as rec:
        init = engine.init_values(part, actions.BFS, {root: 0.0})
        engine.run_stacked(actions.BFS, part, init, cfg)
    rounds = rec.to_session()["rounds"]
    assert rounds and all("window" in r for r in rounds)
    assert rounds[0]["window"] == 1
    assert rounds[0]["grid"] == "device_worklist"


# --------------------------------------------------------------------------
# recorder -> session -> report
# --------------------------------------------------------------------------

def test_session_roundtrip_and_report(tmp_path):
    _, part, root = _small_case()
    with obs.recording(keep_frontiers=False,
                       meta={"case": "bfs-smoke"}) as rec:
        init = engine.init_values(part, actions.BFS, {root: 0.0})
        engine.run_stacked(actions.BFS, part, init,
                           engine.EngineConfig(use_pallas=True))
    path = tmp_path / "session.json"
    rec.save(path)
    session = obs.load_session(path)
    assert session["meta"] == {"case": "bfs-smoke"}
    assert len(session["rounds"]) == len(rec.rounds) > 0
    assert all(sum(r["shard_messages"]) == r["messages"]
               for r in session["rounds"])
    names = {m["name"] for m in session["metrics"]}
    assert {"engine_rounds_total", "engine_messages_total",
            "engine_shard_message_skew"} <= names

    text = report.render(session)
    assert "engine rounds" in text
    assert "bfs" in text
    assert "shard messages" in text and "skew" in text
    assert "trace:" in text


def test_result_cache_invalidation():
    c = ResultCache(size=8)
    c.put(("bfs", (3,)), "a", now=0.0)
    c.put(("bfs", (4,)), "b", now=0.0)
    c.put(("ppr", ((3, 1.0),), 0.85, 1e-6), "c", now=0.0)
    assert c.get(("bfs", (3,)), now=0.0) == "a"
    # root 3 stales both the bfs and the seeded-ppr entry
    assert c.invalidate(3) == 2
    assert c.get(("bfs", (3,)), now=0.0) is None
    assert c.get(("bfs", (4,)), now=0.0) == "b"
    assert c.invalidate_all() == 1
    assert len(c) == 0 and c.invalidations == 3


def test_server_spans_cache_counters_and_invalidation():
    from repro.query import QueryServer
    from repro.serve.admission import ServeConfig
    g, part, root = _small_case(seed=5)
    srv = QueryServer(part, n_lanes=2,
                      cfg=engine.EngineConfig(use_pallas=False),
                      serve=ServeConfig(cache_size=8))
    with obs.recording() as rec:
        q1 = srv.submit("bfs", root)
        srv.run()
        q2 = srv.submit("bfs", root)          # cache hit
        srv.run()
        assert srv.invalidate_cache(root) == 1
        q3 = srv.submit("bfs", root)          # miss again
        srv.run()

    snap = rec.registry.snapshot()
    cache = snap["serve_cache_total"]["series"]
    assert cache[(("event", "hit"),)] == 1
    assert cache[(("event", "miss"),)] == 2
    assert cache[(("event", "invalidation"),)] == 1
    done = snap["serve_requests_total"]["series"]
    assert sum(done.values()) == 3
    assert snap["serve_ticks_total"]["series"][()] > 0

    evs = rec.tracer.events()
    runs = [e for e in evs if e["name"] == "run" and e["ph"] == "X"]
    queued = [e for e in evs if e["name"] == "queued"]
    assert len(runs) == 3 and len(queued) == 3
    qids = {e["args"]["qid"] for e in runs}
    assert qids == {q1, q2, q3}
    assert any(e["args"].get("cached") for e in runs)
    assert any(e["name"] == "tick" for e in evs)


# --------------------------------------------------------------------------
# sharded run: per-shard message skew recorded over real collectives
# --------------------------------------------------------------------------

SHARDED_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro import obs
    from repro.apps.pagerank import _pr_graph, pagerank_delta
    from repro.graph import generators
    from repro.obs import report

    assert len(jax.devices()) == 8
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))

    # BA gives the heavy-tailed in-degree the skew gauge exists for
    g = generators.ba_skewed(400, m_per=4, seed=11)
    with obs.recording(meta={"case": "sharded-skew"}) as rec:
        _, stats, _ = pagerank_delta(g, tol=3e-5, num_shards=8,
                                     rpvo_max=2, mesh=mesh, max_rounds=6)
    rounds = [r for r in rec.rounds if r.run == "pagerank_delta_sharded"]
    assert len(rounds) == int(stats.iterations) > 0
    assert sum(sum(r.shard_messages) for r in rounds) \\
        == int(stats.messages)
    assert all(len(r.shard_messages) == 8 for r in rounds)
    totals = [sum(col) for col in zip(*(r.shard_messages
                                        for r in rounds))]
    skew = max(totals) / (sum(totals) / len(totals))
    assert skew >= 1.0

    snap = rec.registry.snapshot()
    gauge = snap["engine_shard_message_skew"]["series"]
    assert (("run", "pagerank_delta_sharded"),) in gauge

    text = report.render(rec.to_session())
    assert "pagerank_delta_sharded" in text
    assert "shard messages" in text and "skew" in text
    line = next(l for l in text.splitlines() if "skew(max/mean)=" in l)
    assert f"{skew:.2f}" in line
    print("SHARDED_SKEW_OK skew=%.3f" % skew)
""")


def test_sharded_skew_recorded_subprocess():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"    # see test_engine_sharded.py
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_CHILD], env=env,
        capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr}"
    assert "SHARDED_SKEW_OK" in out.stdout


# --------------------------------------------------------------------------
# program spans: obs.span on the profiler's clock and on the recorder
# --------------------------------------------------------------------------

def _profiled(tmp_path, fn):
    """Run ``fn`` under ``jax.profiler`` and return the host events of
    the program's spans as (name, start_ns, end_ns, args) in start order."""
    import glob
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("app.", "engine.")):
                    events.append((ev.name, ev.start_ns, ev.end_ns,
                                   dict(ev.stats)))
    return sorted(events, key=lambda ev: (ev[1], -ev[2]))


def _inside(events, outer, name):
    return [ev for ev in events if ev[0] == name
            and outer[1] <= ev[1] and ev[2] <= outer[2]]


def _syncs_and_dispatches():
    reg = obs.registry()
    return tuple(sum(reg.counter(c).snapshot_values().values())
                 for c in ("engine_host_syncs_total",
                           "engine_dispatches_total"))


def test_program_spans_in_profiler_trace(tmp_path):
    """An app call is one ``app.<name>`` span holding the engine's
    upload, dispatch and sync spans; the BFS host loop dispatches once
    and syncs at least once inside each round; every ``engine.sync`` and
    ``engine.dispatch`` span is counted in the registry; and with no
    recorder installed nothing is recorded on a tracer."""
    from repro import apps
    g, part, root = _small_case()
    cfg = engine.EngineConfig(use_pallas=True, grid_mode="worklist")
    idle = obs.FlightRecorder()           # never installed
    before = _syncs_and_dispatches()
    out = {}

    def calls():
        out["pr"] = apps.pagerank(g, iters=3, part=part, cfg=cfg)
        out["bfs"] = apps.bfs(g, root, part=part, cfg=cfg)

    events = _profiled(tmp_path, calls)
    assert idle.tracer.events() == []
    pr, = [ev for ev in events if ev[0] == "app.pagerank"]
    bfs, = [ev for ev in events if ev[0] == "app.bfs"]
    assert bfs[3]["call"] == pr[3]["call"] + 1
    for name in ("engine.upload", "engine.plan", "engine.dispatch",
                 "engine.sync"):
        assert _inside(events, pr, name), name
    assert len(_inside(events, pr, "engine.dispatch")) == 1

    rounds = _inside(events, bfs, "engine.round")
    assert len(rounds) == int(out["bfs"][1].iterations) > 1
    for rnd in rounds:
        assert len(_inside(events, rnd, "engine.dispatch")) == 1
        assert len(_inside(events, rnd, "engine.sync")) >= 1
    assert _inside(events, bfs, "engine.upload")

    syncs, dispatches = (a - b for a, b in
                         zip(_syncs_and_dispatches(), before))
    assert syncs == sum(ev[0] == "engine.sync" for ev in events)
    assert dispatches == sum(ev[0] == "engine.dispatch" for ev in events)


def test_program_spans_on_recorder():
    """With a recorder installed the same spans land on its tracer."""
    from repro import apps
    g, part, root = _small_case()
    cfg = engine.EngineConfig(use_pallas=True, grid_mode="worklist")
    with obs.recording() as rec:
        apps.bfs(g, root, part=part, cfg=cfg)
        apps.pagerank(g, iters=3, part=part, cfg=cfg)
    evs = rec.tracer.to_chrome()["traceEvents"]
    names = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"app.bfs", "app.pagerank", "engine.upload", "engine.plan",
            "engine.round", "engine.dispatch", "engine.sync"} <= names
    rnd = next(e for e in evs if e["name"] == "engine.round")
    assert {"round", "frontier", "messages"} <= set(rnd["args"])
    plan = [e for e in evs if e["name"] == "engine.plan"
            and "cells" in e.get("args", {})]
    assert plan and all(e["args"]["launched"] >= e["args"]["cells"]
                        for e in plan)


def test_import_obs_pulls_in_no_jax():
    code = ("import sys, repro.obs; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


# --------------------------------------------------------------------------
# stable device names: the exchange layer's named scopes
# --------------------------------------------------------------------------

def _op_names(text):
    import re
    return {part for loc in re.findall(r'loc\("([^"]*)"', text)
            for part in loc.split("/")}


@pytest.mark.parametrize("exchange_mode", ["dense", "compact"])
@pytest.mark.parametrize("app", ["pagerank", "bfs"])
def test_round_op_names_carry_scopes(app, exchange_mode):
    """The lowered stacked PageRank and BFS rounds carry ``relax``,
    ``exchange`` and ``collapse`` in their ``op_name`` metadata."""
    _, part, root = _small_case()
    cfg = engine.EngineConfig(use_pallas=True, exchange=exchange_mode)
    arrays = engine.DeviceArrays.from_partition(part)
    val = jnp.asarray(engine.init_values(part, actions.BFS, {root: 0.0}))
    chg = arrays.slot_valid
    if app == "pagerank":
        def fn(v, c):
            return exchange.pagerank_round_stacked(
                actions.PAGERANK, arrays, cfg, part.S, part.R_max,
                0.15 / part.n, 0.85, v, c)
    else:
        def fn(v, c):
            return exchange.fixpoint_round_stacked(
                actions.BFS, arrays, cfg, part.S, part.R_max, v, c)
    text = jax.jit(fn).lower(val, chg).as_text(debug_info=True)
    assert {"relax", "exchange", "collapse"} <= _op_names(text)
