"""Lane-batched multi-query fixpoint execution (ISSUE 2 tentpole; the
round machinery now lives in the unified exchange layer — ISSUE 3).

The paper's runtime keeps every compute cell busy by letting actions spawn
fine-grain work; serving heavy traffic means the unit of load is *many
concurrent source-rooted queries* over one shared rhizome-partitioned
graph.  Here the engine's value table grows a trailing **query-lane axis
Q**: values are ``(S, R_max, Q)``, the ``changed`` frontier is per-lane,
and one relax round advances every live query at once — the batching
answer to per-query underutilization in vertex-centric systems (iPregel;
Yan et al.), amortizing message/synchronization cost across queries.

Per-lane convergence is free: a lane whose frontier column is all-False
reads as the absorbing identity inside the relax, so it stops relaxing
while the round keeps running for live lanes; the fused kernel's frontier
chunk-skip bitmap becomes the OR across lanes (a grid cell is skipped
only when its edge chunk is dead in *every* lane — see
``kernels.fused_relax_reduce.fused_relax_reduce_lanes_pallas``).

One compiled round serves a **mixed BFS/SSSP batch**: all min-semiring
queries relax with 'add_w', and the per-lane ``lane_unitw`` flag swaps
the edge weight for the constant 1.0 (BFS levels are SSSP distances over
unit weights — the same float op, so a batched lane is bit-identical to
its solo ``engine.run_stacked`` run).  Sum-semiring lanes (personalized
PageRank, per-lane seed/damping) run as counted ``make_ppr_round`` rounds
with a per-lane tolerance-based convergence mask.

Both exchanges serve the lane axis: ``exchange='dense'`` ships the full
(S, R_max, Q) inbox, ``exchange='compact'`` ships only the §Perf
(target, distinct-slot) targeted tables with Q riding as a trailing dim —
converged lanes contribute the absorbing identity and add no message
volume (``LaneStats.exchanged`` accounts the per-lane difference).

Under ``use_pallas`` the laned fused kernel pads the lane axis to the
sublane tile (masked tail lanes, bit-identical for the min pool).
"""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import exchange
from repro.core import actions, engine
from repro.core.actions import Semiring
from repro.core.engine import DeviceArrays, EngineConfig
from repro.core.partition import Partition


UNREACHED = np.iinfo(np.int32).max


def decode_min_values(vv: np.ndarray, kind: str) -> np.ndarray:
    """Decode a min-lane's per-vertex values for its query kind: 'bfs' ->
    int64 levels with the UNREACHED sentinel, 'reachability' -> bool,
    'sssp' -> float64 distances (inf where unreachable).  The single
    decoding point for batched apps and the QueryServer."""
    if kind == "bfs":
        out = np.where(np.isfinite(vv), vv, 0).astype(np.int64)
        out[~np.isfinite(vv)] = UNREACHED
        return out
    if kind == "reachability":
        return np.isfinite(vv)
    if kind == "sssp":
        return vv.astype(np.float64)
    raise ValueError(f"unknown min-lane query kind {kind!r}")


class LaneStats(typing.NamedTuple):
    """Per-lane (Q,) counters — the Fig-6 statistics, one per query, plus
    the §Perf exchange-volume accounting (entries shipped through the
    inter-shard exchange while the lane was live; compact < dense)."""

    rounds: jax.Array        # rounds in which the lane was live
    messages: jax.Array      # actions delivered (active edges) per lane
    work_actions: jax.Array  # predicate-true slot updates per lane
    exchanged: jax.Array     # exchange entries shipped while live per lane


def _zero_stats(q: int) -> LaneStats:
    zero_q = jnp.zeros((q,), jnp.int32)
    return LaneStats(zero_q, zero_q, zero_q, zero_q)


def _check_cfg(cfg: EngineConfig):
    if cfg.collapse != "eager":
        raise ValueError("lane-batched runners support collapse='eager' only")
    if cfg.use_pallas and cfg.pallas_mode != "fused":
        raise ValueError(
            "lane-batched Pallas execution is fused-only (the pre-fusion "
            "'reduce' composition has no laned form)")


def _check_min(sem: Semiring):
    # the laned round relaxes with 'add_w' + the per-lane unitw flag, so a
    # semiring whose own relax differs (e.g. BFS 'add_one') must not be
    # accepted and silently re-relaxed with edge weights — BFS lanes are
    # expressed as lane_unitw=1 under the SSSP semiring instead
    if sem.segment != "min" or sem.relax_kind != "add_w":
        raise ValueError(
            "laned runners drive min-semiring 'add_w' fixpoints (express "
            "BFS lanes with lane_unitw=1, not the 'add_one' semiring); "
            "sum semirings run as make_ppr_round counted rounds")


def _volume(part: Partition, cfg: EngineConfig) -> int:
    return exchange.exchange_volume(part.S, part.R_max, part.P_t, cfg)


def _lane_round_stacked(sem, arrays, cfg, S, R_max, lane_unitw, val, chg):
    """One stacked laned fixpoint round — the unified exchange-layer
    composition (dense or compact) with the lane axis riding along."""
    return exchange.fixpoint_round_stacked(
        sem, arrays, cfg, S, R_max, val, chg, lane_unitw=lane_unitw)


# --------------------------------------------------------------------------
# stacked laned fixpoint runner (BFS / SSSP / reachability / CC lanes)
# --------------------------------------------------------------------------

def make_stacked_lanes_fn(part: Partition | engine.ResidentPartition,
                          cfg: EngineConfig = EngineConfig(),
                          sem: Semiring = actions.SSSP):
    """Builds the stacked laned fixpoint as a jitted fn of ((S, R_max, Q)
    init values, (Q,) lane_unitw, (S, R_max, Q) init changed[, (Q,)
    lane_budget]) -> (values, LaneStats).  Q is encoded in the argument
    shapes, so one returned fn serves any lane count (jit specializes per
    Q).  Hold on to the returned fn to amortize tracing across calls —
    the serving loop and ``benchmarks/query_bench.py`` compile it once.

    ``lane_budget`` ((Q,) int32, optional) is a per-lane round budget:
    a lane that has been live for ``budget`` rounds is frozen in-trace
    (``exchange.fixpoint_round_stacked``'s ``lane_mask``) — its values
    stop improving and it costs no further messages, so a pathological
    query cannot pin the shared fixpoint past its budget."""
    _check_cfg(cfg)
    _check_min(sem)
    res = engine.resident(part)
    part, arrays = res.part, res.arrays
    S, R_max = part.S, part.R_max
    vol = _volume(part, cfg)

    @jax.jit
    def fn(init_val, lane_unitw, init_chg, lane_budget=None):
        q = init_val.shape[-1]

        def body(carry):
            val, chg, it, stats = carry
            live = chg.reshape(-1, q).any(axis=0)
            if lane_budget is None:
                mask = None
            else:
                mask = stats.rounds < lane_budget
                live = live & mask
            new_val, new_chg, counts = exchange.fixpoint_round_stacked(
                sem, arrays, cfg, S, R_max, val, chg,
                lane_unitw=lane_unitw, lane_mask=mask)
            stats = LaneStats(
                rounds=stats.rounds + live.astype(jnp.int32),
                messages=stats.messages + counts,
                work_actions=stats.work_actions
                + new_chg.sum(axis=(0, 1), dtype=jnp.int32),
                exchanged=stats.exchanged + live.astype(jnp.int32) * vol,
            )
            return new_val, new_chg, it + 1, stats

        def cond(carry):
            _, chg, it, stats = carry
            if lane_budget is None:
                anyw = jnp.any(chg)
            else:
                anyw = jnp.any(chg.reshape(-1, q)
                               & (stats.rounds < lane_budget)[None, :])
            return anyw & (it < cfg.max_iters)

        val, chg, it, stats = lax.while_loop(
            cond, body,
            (init_val, init_chg, jnp.zeros((), jnp.int32), _zero_stats(q)))
        return val, stats

    return fn


def _run_stacked_lanes_hostloop(res, cfg, sem, init_val, lane_unitw,
                                init_chg, lane_budget=None):
    """Worklist-mode laned fixpoint: a Python round loop so the
    OR-across-lanes frontier can plan each round's sparse launch —
    identical values and LaneStats to the traced ``while_loop``
    (min lanes are bit-identical).  ``lane_budget`` freezes a lane after
    its budgeted round count, and the worklist planner sees the frozen
    lane as dead (its cells stop launching).  The round program is the
    resident partition's (``engine.ResidentPartition.round_program``),
    so a later call on ``res`` compiles nothing."""
    q = init_val.shape[-1]
    planner = res.planner(cfg)
    vol = _volume(res.part, cfg)
    budget = (None if lane_budget is None
              else np.asarray(lane_budget, np.int64).reshape(q))
    round_fn = res.round_program("lanes_min", exchange.fixpoint_round_stacked,
                                 sem, cfg)

    val, chg = init_val, init_chg
    chg_h = np.asarray(chg).reshape(-1, q)   # ONE download per round
    rounds = np.zeros(q, np.int64)
    messages = np.zeros(q, np.int64)
    work = np.zeros(q, np.int64)
    exchanged = np.zeros(q, np.int64)
    it = 0
    while it < cfg.max_iters:
        mask = None if budget is None else rounds < budget
        eff_chg = chg_h if mask is None else chg_h & mask[None, :]
        live = eff_chg.any(axis=0)
        if not live.any():
            break
        wl = engine.plan_round_worklist(planner, cfg, eff_chg.any(axis=1))
        val, chg, counts = round_fn(
            res.arrays, val, chg, lane_unitw=lane_unitw, worklist=wl,
            lane_mask=None if mask is None else jnp.asarray(mask))
        chg_h = np.asarray(chg).reshape(-1, q)
        rounds += live
        messages += np.asarray(counts, np.int64)
        work += chg_h.sum(axis=0)
        exchanged += live.astype(np.int64) * vol
        it += 1
    engine._count_dispatches("lanes_min", it, it)
    stats = LaneStats(*(jnp.asarray(x, jnp.int32) for x in
                        (rounds, messages, work, exchanged)))
    return val, stats


def run_stacked_lanes(part: Partition | engine.ResidentPartition, init_val,
                      lane_unitw=None,
                      cfg: EngineConfig = EngineConfig(),
                      init_changed=None, sem: Semiring = actions.SSSP,
                      lane_budget=None):
    """Single-device lane-batched execution. ``init_val``: (S, R_max, Q)
    float32 — one query per lane; ``lane_unitw`` (Q,) marks BFS-style
    lanes (relax with weight 1.0).  A lane converges when no slot of its
    column improves; the round keeps running while any lane is live.
    Returns ((S, R_max, Q) values, per-lane ``LaneStats``).

    ``lane_budget`` ((Q,) int, scalar broadcasts) caps each lane's live
    rounds: a budget-exhausted lane freezes (partial values carried
    through, no further cost) while other lanes run to convergence —
    the runner-level face of the QueryServer's per-request round budget.

    Under ``cfg.grid_mode='worklist'|'auto'`` (fused only) rounds run
    host-driven and each round's OR-across-lanes frontier plans a
    sparse worklist launch (see ``engine.run_stacked``); under
    ``'device_worklist'`` the same live-cell launch is compacted ON
    DEVICE, so the whole laned fixpoint stays one traced
    ``while_loop`` dispatch with zero per-round host syncs."""
    init_val = jnp.asarray(init_val, jnp.float32)
    if init_val.ndim != 3:
        raise ValueError(f"init_val must be (S, R_max, Q); got "
                         f"{init_val.shape}")
    q = init_val.shape[-1]
    lane_unitw = (jnp.zeros((q,), jnp.int32) if lane_unitw is None
                  else jnp.asarray(lane_unitw, jnp.int32).reshape(q))
    if lane_budget is not None:
        lane_budget = jnp.broadcast_to(
            jnp.asarray(lane_budget, jnp.int32), (q,))
    res = engine.resident(part)
    slot_valid = res.arrays.slot_valid
    if init_changed is not None:
        init_chg = jnp.asarray(init_changed) & slot_valid[..., None]
    else:
        init_chg = sem.improved(
            init_val, jnp.full_like(init_val, sem.identity)
        ) & slot_valid[..., None]
    if cfg.wants_worklist:
        _check_cfg(cfg)
        _check_min(sem)
        return _run_stacked_lanes_hostloop(
            res, cfg, sem, init_val, lane_unitw, init_chg, lane_budget)
    fn = make_stacked_lanes_fn(res, cfg, sem)
    out = fn(init_val, lane_unitw, init_chg, lane_budget)
    # the traced while_loop (dense grid or device-compacted worklist)
    # was ONE dispatch with one result sync
    engine._count_dispatches("lanes_min", 1, 1)
    return out


# --------------------------------------------------------------------------
# sharded laned fixpoint (shard_map over a real mesh)
# --------------------------------------------------------------------------

def make_sharded_lanes_fn(S: int, R_max: int, Q: int, mesh: Mesh,
                          axis_names=("data", "model"),
                          cfg: EngineConfig = EngineConfig(),
                          sem: Semiring = actions.SSSP,
                          with_init_changed: bool = False):
    """shard_map laned fixpoint as a jit-able fn of (DeviceArrays,
    (S, R_max, Q) val, (Q,) lane_unitw) -> (val, LaneStats).  Same
    collective plan as ``engine.make_sharded_fn`` with the lane axis
    riding along (``exchange.make_shard_fixpoint_round``): value/changed
    all_gather, inbox all_to_all — the full (S, R_max, Q) table under
    ``exchange='dense'``, only the (S, P_t, Q) targeted compact tables
    under ``exchange='compact'`` — sibling collapse over the gathered
    table, per-lane psum'd liveness for the termination test.

    With ``with_init_changed=True`` the returned fn takes a fourth
    argument, an (S, R_max, Q) bool initial frontier, instead of
    deriving it from non-identity values — streaming warm-starts seed
    only the mutation-affected slots this way."""
    _check_cfg(cfg)
    _check_min(sem)
    cfg = engine._sharded_cfg(cfg, "make_sharded_lanes_fn")
    axis_names = exchange.axis_tuple(axis_names)
    spec = P(axis_names)

    in_specs = (
        DeviceArrays.specs(spec),
        spec,
        P(),                                   # lane_unitw: replicated
    )
    if with_init_changed:
        in_specs = in_specs + (spec,)

    def shard_fn(arrays_l: DeviceArrays, val_l, lane_unitw, *rest):
        arrays_s = jax.tree.map(lambda x: x[0], arrays_l)
        val = val_l[0]                         # (R_max, Q)
        vol = exchange.exchange_volume(
            S, R_max, arrays_s.inbox_slot_map.shape[-1], cfg)
        round_fn = exchange.make_shard_fixpoint_round(
            sem, arrays_s, cfg, S, R_max, axis_names,
            lane_unitw=lane_unitw)

        def body(carry):
            val, chg, it, stats = carry
            live = lax.psum(
                chg.reshape(-1, Q).any(axis=0).astype(jnp.int32),
                axis_names) > 0
            new_val, new_chg, counts = round_fn(val, chg)
            stats = LaneStats(
                rounds=stats.rounds + live.astype(jnp.int32),
                messages=stats.messages + lax.psum(counts, axis_names),
                work_actions=stats.work_actions + lax.psum(
                    new_chg.sum(axis=0, dtype=jnp.int32), axis_names),
                exchanged=stats.exchanged + live.astype(jnp.int32) * vol,
            )
            return new_val, new_chg, it + 1, stats

        def cond(carry):
            _, chg, it, _ = carry
            anyc = lax.psum(chg.any().astype(jnp.int32), axis_names)
            return (anyc > 0) & (it < cfg.max_iters)

        if with_init_changed:
            init_chg = rest[0][0] & arrays_s.slot_valid[..., None]
        else:
            init_chg = (
                sem.improved(val, jnp.full_like(val, sem.identity))
                & arrays_s.slot_valid[..., None]
            )
        val, chg, it, stats = lax.while_loop(
            cond, body,
            (val, init_chg, jnp.zeros((), jnp.int32), _zero_stats(Q)))
        return val[None], jax.tree.map(lambda x: x[None], stats)

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=in_specs,
        out_specs=(spec, LaneStats(*([spec] * 4))),
        check_vma=False,
    )
    return jax.jit(fn), NamedSharding(mesh, spec)


def make_sharded_min_round(S: int, R_max: int, mesh: Mesh,
                           axis_names=("data", "model"),
                           cfg: EngineConfig = EngineConfig(),
                           sem: Semiring = actions.SSSP):
    """shard_map laned fixpoint round: (DeviceArrays, val, chg, unitw) ->
    (val, chg, (Q,) psum'd counts) — one tick of the sharded
    QueryServer's min pool (``make_sharded_lanes_fn`` runs the same round
    inside a traced while_loop; the server needs it un-looped so it can
    inject/evict lanes between ticks).  Counterpart of
    ``make_sharded_ppr_round`` for the sum pool."""
    _check_cfg(cfg)
    _check_min(sem)
    cfg = engine._sharded_cfg(cfg, "make_sharded_min_round")
    axis_names = exchange.axis_tuple(axis_names)
    spec = P(axis_names)

    in_specs = (
        DeviceArrays.specs(spec),
        spec, spec,
        P(),                                   # lane_unitw: replicated
    )

    def shard_fn(arrays_l: DeviceArrays, val_l, chg_l, unitw):
        arrays_s = jax.tree.map(lambda x: x[0], arrays_l)
        round_fn = exchange.make_shard_fixpoint_round(
            sem, arrays_s, cfg, S, R_max, axis_names, lane_unitw=unitw)
        cand, new_chg, counts = round_fn(val_l[0], chg_l[0])
        counts = lax.psum(counts, axis_names)
        return cand[None], new_chg[None], counts[None]

    fn = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs,
        out_specs=(spec, spec, spec), check_vma=False,
    )
    return jax.jit(fn), NamedSharding(mesh, spec)


def run_sharded_lanes(part: Partition, init_val, lane_unitw=None,
                      mesh: Mesh = None, axis_names=("data", "model"),
                      cfg: EngineConfig = EngineConfig(),
                      sem: Semiring = actions.SSSP,
                      init_changed=None):
    """shard_map laned execution; layout as in ``engine.run_sharded``.
    ``init_changed`` optionally seeds the first frontier (streaming
    warm-starts); default derives it from non-identity values."""
    init_val = jnp.asarray(init_val, jnp.float32)
    q = init_val.shape[-1]
    lane_unitw = (np.zeros((q,), np.int32) if lane_unitw is None
                  else np.asarray(lane_unitw, np.int32).reshape(q))
    fn, sharding = make_sharded_lanes_fn(
        part.S, part.R_max, q, mesh, axis_names, cfg, sem,
        with_init_changed=init_changed is not None)
    arrays = DeviceArrays.from_partition(part)
    arrays_dev = jax.tree.map(lambda x: jax.device_put(x, sharding), arrays)
    val_dev = jax.device_put(init_val, sharding)
    if init_changed is not None:
        chg_dev = jax.device_put(jnp.asarray(init_changed, bool), sharding)
        val, stats = fn(arrays_dev, val_dev, jnp.asarray(lane_unitw),
                        chg_dev)
    else:
        val, stats = fn(arrays_dev, val_dev, jnp.asarray(lane_unitw))
    stats = jax.tree.map(lambda x: x[0], stats)
    return val, stats


# --------------------------------------------------------------------------
# personalized-PageRank lanes (sum semiring, per-lane seed/damping)
# --------------------------------------------------------------------------

def make_ppr_round(part: Partition, cfg: EngineConfig = EngineConfig(),
                   arrays: DeviceArrays | None = None):
    """Builds the jitted laned PPR round: (val, base, damping, live) ->
    (new_val, (Q,) max-abs delta, (Q,) message counts).  Pass ``arrays``
    to share one device copy of the static graph tables with other
    round fns over the same partition (the QueryServer does).

    One round is relax(mul_w) -> exchange (dense or compact targeted) ->
    rhizome-collapse(+) over the inbox -> per-lane damping update
    ``base + d_q * total_in``; ``base`` is the per-lane personalization
    table ((1-d_q) at the seed's replicas — see ``ppr_base_table``).
    ``live`` (Q,) freezes converged lanes: their frontier column is
    masked off (they cost no messages) and their values are carried
    through unchanged, so a lane evicted by the server stays bit-stable
    while other lanes keep iterating."""
    _check_cfg(cfg)
    if arrays is None:
        arrays = DeviceArrays.from_partition(part)
    S, R_max = part.S, part.R_max
    sem = actions.PAGERANK
    total = S * R_max

    def round_fn(val, base, damping, live):
        q = val.shape[-1]
        gchg = (arrays.slot_valid[..., None] & live[None, None, :]) \
            .reshape(total, q)
        total_in, counts = exchange.stacked_total_in(
            sem, arrays, cfg, S, R_max, val.reshape(total, q), gchg)
        new = jnp.where(arrays.slot_valid[..., None],
                        base + damping[None, None, :] * total_in, 0.0)
        new = jnp.where(live[None, None, :], new, val)
        delta = jnp.abs(new - val).max(axis=(0, 1))
        return new, delta, counts

    return jax.jit(round_fn)


def make_sharded_ppr_round(S: int, R_max: int, mesh: Mesh,
                           axis_names=("data", "model"),
                           cfg: EngineConfig = EngineConfig()):
    """shard_map laned PPR round: (DeviceArrays, val, base, damping, live)
    -> (new_val, (Q,) max-abs delta, (Q,) counts) — one counted round of
    the sharded serving loop, same semantics as ``make_ppr_round`` with
    real collectives (delta is pmax'd, counts psum'd across the mesh).
    The lane count is taken from the traced argument shapes, so one
    returned fn serves any Q (jit specializes per shape)."""
    _check_cfg(cfg)
    cfg = engine._sharded_cfg(cfg, "make_sharded_ppr_round")
    axis_names = exchange.axis_tuple(axis_names)
    sem = actions.PAGERANK
    spec = P(axis_names)

    in_specs = (
        DeviceArrays.specs(spec),
        spec, spec,
        P(),                                   # damping: replicated
        P(),                                   # live: replicated
    )

    def shard_fn(arrays_l: DeviceArrays, val_l, base_l, damping, live):
        arrays_s = jax.tree.map(lambda x: x[0], arrays_l)
        val, base = val_l[0], base_l[0]        # (R_max, Q)

        def gather(x):
            return lax.all_gather(x, axis_names, tiled=True)

        chg = arrays_s.slot_valid[..., None] & live[None, :]
        total_in, counts = exchange.shard_total_in(
            sem, arrays_s, cfg, S, R_max, axis_names,
            gather(val), gather(chg))
        new = jnp.where(arrays_s.slot_valid[..., None],
                        base + damping[None, :] * total_in, 0.0)
        new = jnp.where(live[None, :], new, val)
        delta = lax.pmax(jnp.abs(new - val).max(axis=0), axis_names)
        counts = lax.psum(counts, axis_names)
        return new[None], delta[None], counts[None]

    fn = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs,
        out_specs=(spec, spec, spec), check_vma=False,
    )
    return jax.jit(fn), NamedSharding(mesh, spec)


def make_sharded_ppr_delta_round(S: int, R_max: int, mesh: Mesh,
                                 axis_names=("data", "model"),
                                 cfg: EngineConfig = EngineConfig()):
    """shard_map laned **delta-PPR** round: (DeviceArrays, rank, delta,
    damping, tol) -> (new_rank, new_delta, new_changed, (Q,) psum'd
    counts) — the sharded twin of ``make_ppr_delta_round``, closing the
    ROADMAP leftover that the sharded PPR pool still ran full-frontier
    rounds.  Each lane diffuses only residual deltas above its own
    tolerance (value/frontier ``all_gather``, inbox exchange — dense or
    compact per ``cfg.exchange`` — rhizome-collapse(+)), so the serving
    tick's relax work shrinks as lanes converge exactly like the stacked
    delta path.  ``new_changed`` is returned sharded so the server's
    per-tick liveness probe never recomputes the predicate host-side."""
    _check_cfg(cfg)
    cfg = engine._sharded_cfg(cfg, "make_sharded_ppr_delta_round")
    axis_names = exchange.axis_tuple(axis_names)
    sem = actions.PAGERANK
    spec = P(axis_names)

    in_specs = (
        DeviceArrays.specs(spec),
        spec, spec,
        P(),                                   # damping: replicated
        P(),                                   # tol: replicated
    )

    def shard_fn(arrays_l: DeviceArrays, rank_l, delta_l, damping, tol):
        arrays_s = jax.tree.map(lambda x: x[0], arrays_l)
        rank, delta = rank_l[0], delta_l[0]    # (R_max, Q)

        def gather(x):
            return lax.all_gather(x, axis_names, tiled=True)

        chg = (jnp.abs(delta) > tol[None, :]) \
            & arrays_s.slot_valid[..., None]
        total_in, counts = exchange.shard_total_in(
            sem, arrays_s, cfg, S, R_max, axis_names,
            gather(delta), gather(chg))
        new_delta = jnp.where(arrays_s.slot_valid[..., None],
                              damping[None, :] * total_in, 0.0)
        new_chg = (jnp.abs(new_delta) > tol[None, :]) \
            & arrays_s.slot_valid[..., None]
        counts = lax.psum(counts, axis_names)
        return ((rank + new_delta)[None], new_delta[None], new_chg[None],
                counts[None])

    fn = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs,
        out_specs=(spec, spec, spec, spec), check_vma=False,
    )
    return jax.jit(fn), NamedSharding(mesh, spec)


def run_ppr_lanes(part: Partition, seeds, dampings,
                  cfg: EngineConfig = EngineConfig(), tol: float = 1e-6,
                  max_rounds: int = 256):
    """Lane-batched personalized PageRank to tolerance.  ``seeds``: one
    personalization vertex per lane; ``dampings``: per-lane damping
    (scalar broadcasts).  A lane converges when its max-abs score delta
    drops to ``tol``; live lanes keep the shared round busy.  Returns
    ((S, R_max, Q) scores, per-lane ``LaneStats``)."""
    q = len(seeds)
    dampings = np.broadcast_to(np.asarray(dampings, np.float32), (q,)).copy()
    base = ppr_base_table(part, seeds, dampings)
    val0 = np.stack(
        [engine.init_values(part, actions.PAGERANK, {int(s): 1.0})
         for s in seeds], axis=-1).astype(np.float32)
    round_fn = make_ppr_round(part, cfg)
    vol = _volume(part, cfg)
    n_slots = jnp.sum(jnp.asarray(part.slot_vertex >= 0), dtype=jnp.int32)

    def body(carry):
        val, live, it, stats = carry
        new_val, delta, counts = round_fn(
            val, jnp.asarray(base), jnp.asarray(dampings), live)
        stats = LaneStats(
            rounds=stats.rounds + live.astype(jnp.int32),
            messages=stats.messages + counts,
            work_actions=stats.work_actions
            + live.astype(jnp.int32) * n_slots,
            exchanged=stats.exchanged + live.astype(jnp.int32) * vol,
        )
        return new_val, live & (delta > tol), it + 1, stats

    def cond(carry):
        _, live, it, _ = carry
        return jnp.any(live) & (it < max_rounds)

    val, live, it, stats = lax.while_loop(
        cond, body,
        (jnp.asarray(val0), jnp.ones((q,), bool), jnp.zeros((), jnp.int32),
         _zero_stats(q)))
    return val, stats


def make_ppr_delta_round(part: Partition,
                         cfg: EngineConfig = EngineConfig(),
                         arrays: DeviceArrays | None = None):
    """Builds the jitted laned **delta-PPR** round: (rank, delta,
    damping, tol, worklist) -> (new_rank, new_delta, new_changed,
    (Q,) counts) — ``new_changed`` is the next round's per-lane
    frontier, returned so the driver never recomputes (or re-downloads)
    the (S, R_max, Q) predicate host-side.

    The laned twin of ``exchange.delta_pagerank_round_stacked``: each
    lane propagates only residual deltas above its own tolerance, so the
    per-lane frontier — and with it the OR-across-lanes chunk skip and
    any worklist launch — shrinks as lanes converge, instead of every
    lane diffusing every slot every round (``make_ppr_round``)."""
    _check_cfg(cfg)
    if arrays is None:
        arrays = DeviceArrays.from_partition(part)
    S, R_max = part.S, part.R_max
    sem = actions.PAGERANK
    total = S * R_max

    @jax.jit
    def round_fn(rank, delta, damping, tol, worklist=None):
        q = rank.shape[-1]
        chg = (jnp.abs(delta) > tol[None, None, :]) \
            & arrays.slot_valid[..., None]
        total_in, counts = exchange.stacked_total_in(
            sem, arrays, cfg, S, R_max, delta.reshape(total, q),
            chg.reshape(total, q), worklist=worklist)
        new_delta = jnp.where(arrays.slot_valid[..., None],
                              damping[None, None, :] * total_in, 0.0)
        new_chg = (jnp.abs(new_delta) > tol[None, None, :]) \
            & arrays.slot_valid[..., None]
        return rank + new_delta, new_delta, new_chg, counts

    return round_fn


def run_ppr_delta_lanes(part: Partition, seeds, dampings,
                        cfg: EngineConfig = EngineConfig(), tol=1e-7,
                        max_rounds: int = 256):
    """Lane-batched delta-PPR to tolerance: like ``run_ppr_lanes`` but
    push-based over residuals — a lane's frontier is the slots whose
    delta still exceeds its ``tol`` (scalar broadcasts; per-lane array
    accepted), so late rounds diffuse only the few still-hot vertices of
    the few still-live lanes.  Host-driven (the per-lane frontier steers
    termination and, under ``grid_mode='worklist'|'auto'``, the sparse
    launch plan).  Under ``'device_worklist'`` the residual-tolerance
    frontier test and worklist compaction both run on device, so the
    whole multi-lane fixpoint is ONE traced dispatch."""
    q = len(seeds)
    dampings = np.broadcast_to(
        np.asarray(dampings, np.float32), (q,)).copy()
    tols = np.broadcast_to(np.asarray(tol, np.float32), (q,)).copy()
    base = ppr_base_table(part, seeds, dampings)
    rank = delta = jnp.asarray(base)
    round_fn = make_ppr_delta_round(part, cfg)
    planner = (engine.launch_planner(part, cfg)
               if cfg.wants_worklist else None)
    vol = _volume(part, cfg)
    slot_valid = np.asarray(part.slot_vertex >= 0)

    if cfg.wants_device_worklist:
        damp_j, tol_j = jnp.asarray(dampings), jnp.asarray(tols)
        sv = jnp.asarray(slot_valid)[..., None]
        vol_j = jnp.asarray(vol, jnp.int32)

        @jax.jit
        def fixpoint(rank, delta):
            def body(carry):
                rank, delta, it, stats = carry
                live = ((jnp.abs(delta) > tol_j[None, None, :]) & sv) \
                    .reshape(-1, q).any(axis=0)
                nrank, ndelta, nchg, counts = round_fn(
                    rank, delta, damp_j, tol_j)
                stats = LaneStats(
                    rounds=stats.rounds + live.astype(jnp.int32),
                    messages=stats.messages + counts.astype(jnp.int32),
                    work_actions=stats.work_actions
                    + nchg.sum(axis=(0, 1), dtype=jnp.int32),
                    exchanged=stats.exchanged
                    + live.astype(jnp.int32) * vol_j,
                )
                return nrank, ndelta, it + 1, stats

            def cond(carry):
                _, delta, it, _ = carry
                anyc = jnp.any((jnp.abs(delta) > tol_j[None, None, :])
                               & sv)
                return anyc & (it < max_rounds)

            rank, delta, _, stats = lax.while_loop(
                cond, body,
                (rank, delta, jnp.zeros((), jnp.int32), _zero_stats(q)))
            return rank, stats

        rank, stats = fixpoint(rank, delta)
        engine._count_dispatches("ppr_delta_lanes", 1, 1)
        return rank, stats

    rounds = np.zeros(q, np.int64)
    messages = np.zeros(q, np.int64)
    work = np.zeros(q, np.int64)
    exchanged = np.zeros(q, np.int64)
    it = 0
    damp_j, tol_j = jnp.asarray(dampings), jnp.asarray(tols)
    # each round returns next round's per-lane frontier — computed on
    # device, downloaded ONCE per round for planning + accounting alike
    chg_h = (np.abs(base) > tols[None, None, :]) & slot_valid[..., None]
    while it < max_rounds:
        live = chg_h.any(axis=(0, 1))
        if not live.any():
            break
        wl = (engine.plan_round_worklist(
            planner, cfg, chg_h.reshape(-1, q).any(axis=1))
            if planner is not None else None)
        rank, delta, chg, counts = round_fn(rank, delta, damp_j, tol_j, wl)
        chg_h = np.asarray(chg)
        rounds += live
        messages += np.asarray(counts, np.int64)
        work += chg_h.sum(axis=(0, 1))
        exchanged += live.astype(np.int64) * vol
        it += 1
    engine._count_dispatches("ppr_delta_lanes", it, it)
    stats = LaneStats(*(jnp.asarray(x, jnp.int32) for x in
                        (rounds, messages, work, exchanged)))
    return rank, stats


# --------------------------------------------------------------------------
# lane state builders (also used by the QueryServer's masked injection)
# --------------------------------------------------------------------------

def init_lane_values(part: Partition, queries) -> tuple[np.ndarray,
                                                        np.ndarray]:
    """Builds ((S, R_max, Q) init values, (Q,) lane_unitw) for a batch of
    min-semiring queries.  ``queries``: list of ("bfs" | "sssp",
    sources) where sources is a vertex, a list of vertices (multi-source:
    all seeded at 0), or a {vertex: value} dict."""
    vals, unitw = [], []
    for kind, sources in queries:
        if kind not in ("bfs", "sssp"):
            raise ValueError(f"unknown min-lane query kind {kind!r}")
        if isinstance(sources, dict):
            src = {int(v): float(x) for v, x in sources.items()}
        elif isinstance(sources, (list, tuple, np.ndarray)):
            src = {int(v): 0.0 for v in sources}
        else:
            src = {int(sources): 0.0}
        vals.append(engine.init_values(part, actions.SSSP, src))
        unitw.append(1 if kind == "bfs" else 0)
    return (np.stack(vals, axis=-1).astype(np.float32),
            np.asarray(unitw, np.int32))


def ppr_base_table(part: Partition, seeds, dampings) -> np.ndarray:
    """(S, R_max, Q) per-lane personalization base: (1 - d_q) at every
    replica of lane q's seed vertex (consistent view), 0 elsewhere."""
    q = len(seeds)
    dampings = np.broadcast_to(np.asarray(dampings, np.float32), (q,))
    cols = [engine.init_values(part, actions.PAGERANK,
                               {int(s): float(1.0 - d)})
            for s, d in zip(seeds, dampings)]
    return np.stack(cols, axis=-1).astype(np.float32)
