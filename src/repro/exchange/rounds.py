"""Round compositions over the lane-generic exchange primitives.

One relax → exchange → rhizome-collapse composition per execution layout
(stacked / shard_map) and per app class (monotone fixpoint / counted
PageRank-style rounds), each serving both the unlaned ``(V,)`` and the
lane-batched ``(V, Q)`` table layouts.  ``core.engine`` and
``query.lanes`` are thin drivers over these — the while/fori loop,
termination collective, and stats bookkeeping live there; the per-round
math lives here, once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.actions import Semiring
from repro.exchange.primitives import (
    collapse, compact_collapse, reduce_axis0, relax, scatter_inbox,
    stacked_compact_partial, stacked_dense_inbox,
)


def axis_tuple(axis_names):
    return axis_names if isinstance(axis_names, tuple) else (axis_names,)


def _flat(table):
    """Collapse the leading (shard, slot) dims; trailing Q rides."""
    return table.reshape((-1,) + table.shape[2:])


# --------------------------------------------------------------------------
# stacked layout: all shards resident as a leading S axis on one device
# --------------------------------------------------------------------------

def stacked_inbox(sem: Semiring, arrays, cfg, S: int, R_max: int,
                  gval, gchg, lane_unitw=None, worklist=None):
    """Relax + exchange on the stacked layout.

    Dense: one reduced global inbox.  Compact (§Perf targeted): per-source
    (target, distinct-slot) partials, axis-swapped in place of the real
    ``all_to_all``, scatter-combined per target.  Returns the
    ((S, R_max[, Q]) inbox, message count — scalar or (Q,)).

    ``worklist`` is a host-planned sparse launch for the fused relax —
    planned against THIS exchange's launch shape (the compact path's
    offset ids differ from the dense flat ids; see
    ``core.engine.launch_planner``)."""
    if cfg.exchange == "compact":
        P_t = arrays.inbox_slot_map.shape[-1]
        partial, counts = stacked_compact_partial(
            sem, arrays, cfg, S, P_t, gval, gchg, lane_unitw, worklist)
        recv = jnp.swapaxes(partial, 0, 1)       # (S_tgt, S_src, P_t[, Q])
        inbox = jax.vmap(lambda r, m: scatter_inbox(sem, r, m, R_max))(
            recv, arrays.inbox_slot_map)
        return inbox, counts
    flat, counts = stacked_dense_inbox(
        sem, arrays, cfg, gval, gchg, S * R_max, lane_unitw, worklist)
    return flat.reshape((S, R_max) + flat.shape[1:]), counts


def stacked_collapse(sem: Semiring, arrays, cfg, table):
    """Eager rhizome collapse of a stacked (S, R_max[, Q]) table — dense
    sibling collapse, or the compact rhizome-only gather/scatter."""
    if cfg.exchange == "compact":
        R_max = table.shape[1]
        return compact_collapse(
            sem, table, arrays.rz_local, arrays.rz_sibling_idx,
            arrays.rz_sibling_mask, _flat, R_max,
            arrays.rz_local.shape[-1])
    out = collapse(sem, _flat(table), arrays.sibling_flat,
                   arrays.sibling_mask)
    return out


def fixpoint_round_stacked(sem: Semiring, arrays, cfg, S: int, R_max: int,
                           val, chg, lane_unitw=None, worklist=None,
                           lane_mask=None):
    """One stacked fixpoint round: relax → exchange → combine → eager
    rhizome collapse → predicate.  ``val``/``chg``: (S, R_max) or
    (S, R_max, Q).  Returns (new val, new changed, message count).

    ``lane_mask`` ((Q,) bool) freezes masked-off lanes for this round —
    their frontier reads all-False (no relax work, no messages) and they
    emit no next-round frontier, while their values carry through
    unchanged.  This is the per-request round-budget plumbing: a lane
    past its budget is silenced in-round instead of torn down."""
    laned = val.ndim == 3
    if lane_mask is not None:
        chg = chg & lane_mask[None, None, :]
    gval, gchg = _flat(val), _flat(chg)
    inbox, counts = stacked_inbox(
        sem, arrays, cfg, S, R_max, gval, gchg, lane_unitw, worklist)
    cand = sem.combine(val, inbox)
    if cfg.collapse == "eager":
        cand = stacked_collapse(sem, arrays, cfg, cand)
    slot = arrays.slot_valid[..., None] if laned else arrays.slot_valid
    new_chg = sem.improved(cand, val) & slot
    if lane_mask is not None:
        cand = jnp.where(lane_mask[None, None, :], cand, val)
        new_chg = new_chg & lane_mask[None, None, :]
    return cand, new_chg, counts


def stacked_total_in(sem: Semiring, arrays, cfg, S: int, R_max: int,
                     gval, gchg, lane_unitw=None, worklist=None):
    """Relax → exchange → rhizome-collapse(⊕) of the *bare inbox* — the
    total in-flow per slot that counted (PageRank-style) rounds consume.
    The collapse sees inbox partials, never combined candidates, so the
    sum-semiring sibling-total overwrite is exact."""
    inbox, counts = stacked_inbox(
        sem, arrays, cfg, S, R_max, gval, gchg, lane_unitw, worklist)
    return stacked_collapse(sem, arrays, cfg, inbox), counts


def pagerank_round_stacked(sem: Semiring, arrays, cfg, S: int, R_max: int,
                           base, damping, val, chg, worklist=None):
    """One stacked PageRank round: relax → exchange → rhizome-collapse(+)
    → damping update.  Shared by run_pagerank_stacked and the engine
    benchmark so BENCH numbers measure the shipped hot path."""
    total_in, counts = stacked_total_in(
        sem, arrays, cfg, S, R_max, _flat(val), _flat(chg),
        worklist=worklist)
    new_val = jnp.where(arrays.slot_valid, base + damping * total_in, 0.0)
    return new_val, counts


def delta_pagerank_round_stacked(sem: Semiring, arrays, cfg, S: int,
                                 R_max: int, damping, tol, rank, delta,
                                 worklist=None):
    """One stacked **delta-PageRank** round (the diffusion-pruned sum
    semiring, paper Listing 10 with lazy residuals).

    Ranks follow the Neumann series ``rank = Σ_k (d·Aᵀ)^k base`` — the
    same fixpoint as the dense power iteration — but each round ships
    only the *residual delta*, and only where it still exceeds ``tol``
    (scalar or per-slot): the frontier ``|delta| > tol`` masks the relax
    (absolute value, so streaming's negative incremental corrections
    diffuse too; cold deltas are nonnegative, making this bit-identical),
    sub-tolerance residuals are dropped (the paper's pruned diffusions),
    and the sum semiring finally has a genuinely shrinking frontier for
    the chunk-skip / worklist / tile-filter stack to prune against.

    Returns (new rank, new delta, new changed, message count); callers
    seed ``rank = delta = base`` (see ``engine.run_pagerank_delta``)."""
    chg = (jnp.abs(delta) > tol) & arrays.slot_valid
    total_in, counts = stacked_total_in(
        sem, arrays, cfg, S, R_max, _flat(delta), _flat(chg),
        worklist=worklist)
    new_delta = jnp.where(arrays.slot_valid, damping * total_in, 0.0)
    new_rank = rank + new_delta
    new_chg = (jnp.abs(new_delta) > tol) & arrays.slot_valid
    return new_rank, new_delta, new_chg, counts


# --------------------------------------------------------------------------
# K-round windows: whole round sequences inside ONE traced dispatch
# --------------------------------------------------------------------------
# The device-resident loop machinery (ISSUE 8): `lax.scan` the stacked
# round bodies K times so drivers dispatch once per WINDOW instead of
# once per round.  A round whose entering frontier is empty is a no-op
# under every semiring here (all sources read the absorbing identity,
# min candidates equal val, delta residuals are zero), so windows that
# overrun convergence stay exact — drivers trim the trailing dead
# rounds from the returned per-round stacks.  Each step also emits the
# frontier ENTERING that round, giving the host the full trajectory for
# post-hoc planner-mirror accounting with zero extra syncs.


def fixpoint_window_stacked(sem: Semiring, arrays, cfg, S: int, R_max: int,
                            k: int, val, chg, lane_unitw=None,
                            lane_mask=None):
    """K stacked fixpoint rounds under one ``lax.scan``.  Returns
    (val, chg, (k[, Q]) per-round message counts, (k, S, R_max[, Q])
    per-round entering frontiers)."""

    def step(carry, _):
        val, chg = carry
        nval, nchg, counts = fixpoint_round_stacked(
            sem, arrays, cfg, S, R_max, val, chg, lane_unitw,
            lane_mask=lane_mask)
        return (nval, nchg), (counts, chg)

    (val, chg), (counts, frontiers) = lax.scan(
        step, (val, chg), None, length=k)
    return val, chg, counts, frontiers


def delta_pagerank_window_stacked(sem: Semiring, arrays, cfg, S: int,
                                  R_max: int, k: int, damping, tol, rank,
                                  delta):
    """K stacked delta-PageRank rounds under one ``lax.scan``.  Returns
    (rank, delta, chg, (k,) counts, (k, S, R_max) entering frontiers)."""

    def step(carry, _):
        rank, delta = carry
        chg = (jnp.abs(delta) > tol) & arrays.slot_valid
        nr, nd, _, counts = delta_pagerank_round_stacked(
            sem, arrays, cfg, S, R_max, damping, tol, rank, delta)
        return (nr, nd), (counts, chg)

    (rank, delta), (counts, frontiers) = lax.scan(
        step, (rank, delta), None, length=k)
    new_chg = (jnp.abs(delta) > tol) & arrays.slot_valid
    return rank, delta, new_chg, counts, frontiers


# --------------------------------------------------------------------------
# shard_map layout: one shard per device, real collectives
# --------------------------------------------------------------------------

def shard_inbox(sem: Semiring, arrays_s, cfg, S: int, R_max: int,
                axis_names, gval, gchg, lane_unitw=None):
    """Per-shard relax + real inbox exchange.

    Dense: (S, R_max[, Q]) partial → ``all_to_all`` → axis-0 reduce.
    Compact: only (target, distinct-slot) contributions travel — the
    (S, P_t[, Q]) targeted tables ride the ``all_to_all`` and scatter
    into local slots.  Returns ((R_max[, Q]) inbox, message count)."""
    if cfg.exchange == "compact":
        P_t = arrays_s.inbox_slot_map.shape[-1]
        partial, counts = relax(
            sem, cfg, arrays_s.edge_src_root_flat, arrays_s.edge_w,
            arrays_s.edge_mask, arrays_s.edge_dst_compact, gval, gchg,
            S * P_t, lane_unitw)
        recv = lax.all_to_all(
            partial.reshape((S, P_t) + partial.shape[1:]), axis_names,
            split_axis=0, concat_axis=0, tiled=True)
        inbox = scatter_inbox(sem, recv, arrays_s.inbox_slot_map, R_max)
        return inbox, counts
    partial, counts = relax(
        sem, cfg, arrays_s.edge_src_root_flat, arrays_s.edge_w,
        arrays_s.edge_mask, arrays_s.edge_dst_flat, gval, gchg,
        S * R_max, lane_unitw)
    # inbox exchange: row t of `partial` belongs to shard t
    recv = lax.all_to_all(
        partial.reshape((S, R_max) + partial.shape[1:]), axis_names,
        split_axis=0, concat_axis=0, tiled=True)
    return reduce_axis0(sem, recv), counts


def shard_collapse(sem: Semiring, arrays_s, cfg, table, gather, R_max: int):
    """Eager rhizome collapse of a per-shard (R_max[, Q]) table; ``gather``
    is the tiled ``all_gather`` over the mesh axes."""
    if cfg.exchange == "compact":
        return compact_collapse(
            sem, table, arrays_s.rz_local, arrays_s.rz_sibling_idx,
            arrays_s.rz_sibling_mask, gather, R_max,
            arrays_s.rz_local.shape[-1])
    return collapse(sem, gather(table), arrays_s.sibling_flat,
                    arrays_s.sibling_mask)


def make_shard_fixpoint_round(sem: Semiring, arrays_s, cfg, S: int,
                              R_max: int, axis_names, lane_unitw=None):
    """Builds the per-shard fixpoint round body (runs inside shard_map):
    (val, chg[, lane_mask]) → (new val, new changed, message count), with
    the same collective plan for unlaned (R_max,) and laned (R_max, Q)
    tables — value/changed ``all_gather`` (the diffusion fan-out), inbox
    ``all_to_all``, sibling collapse over the gathered table.

    The optional ``lane_mask`` ((Q,) bool, replicated) is the round-budget
    plumbing (see ``fixpoint_round_stacked``): masked-off lanes relax
    nothing, ship nothing, and carry their values through unchanged."""
    axis_names = axis_tuple(axis_names)

    def gather(x):
        return lax.all_gather(x, axis_names, tiled=True)

    def round_fn(val, chg, lane_mask=None):
        laned = val.ndim == 2
        if lane_mask is not None:
            chg = chg & lane_mask[None, :]
        gval, gchg = gather(val), gather(chg)
        inbox, counts = shard_inbox(
            sem, arrays_s, cfg, S, R_max, axis_names, gval, gchg,
            lane_unitw)
        cand = sem.combine(val, inbox)
        if cfg.collapse == "eager":
            cand = shard_collapse(sem, arrays_s, cfg, cand, gather, R_max)
        slot = arrays_s.slot_valid[..., None] if laned \
            else arrays_s.slot_valid
        new_chg = sem.improved(cand, val) & slot
        if lane_mask is not None:
            cand = jnp.where(lane_mask[None, :], cand, val)
            new_chg = new_chg & lane_mask[None, :]
        return cand, new_chg, counts

    return round_fn


def shard_total_in(sem: Semiring, arrays_s, cfg, S: int, R_max: int,
                   axis_names, gval, gchg, lane_unitw=None):
    """Sharded relax → exchange → rhizome-collapse(⊕) of the bare inbox
    (see ``stacked_total_in``)."""
    axis_names = axis_tuple(axis_names)

    def gather(x):
        return lax.all_gather(x, axis_names, tiled=True)

    inbox, counts = shard_inbox(
        sem, arrays_s, cfg, S, R_max, axis_names, gval, gchg, lane_unitw)
    return shard_collapse(sem, arrays_s, cfg, inbox, gather, R_max), counts


def delta_pagerank_round_shard(sem: Semiring, arrays_s, cfg, S: int,
                               R_max: int, axis_names, damping, tol,
                               rank, delta):
    """Per-shard delta-PageRank round (runs inside shard_map): the
    sharded twin of ``delta_pagerank_round_stacked`` — value/frontier
    ``all_gather``, relax over the shrinking residual frontier, inbox
    exchange, rhizome-collapse(+).  Counts are local (callers psum)."""
    axis_names = axis_tuple(axis_names)

    def gather(x):
        return lax.all_gather(x, axis_names, tiled=True)

    chg = (jnp.abs(delta) > tol) & arrays_s.slot_valid
    total_in, counts = shard_total_in(
        sem, arrays_s, cfg, S, R_max, axis_names, gather(delta),
        gather(chg))
    new_delta = jnp.where(arrays_s.slot_valid, damping * total_in, 0.0)
    new_rank = rank + new_delta
    new_chg = (jnp.abs(new_delta) > tol) & arrays_s.slot_valid
    return new_rank, new_delta, new_chg, counts


# --------------------------------------------------------------------------
# host-side accounting mirrors (flight-recorder feeds; never traced)
# --------------------------------------------------------------------------

def shard_message_mirror(edge_mask, edge_src_root_flat, gchg):
    """Per-shard message-volume mirror: how many live edge messages each
    shard's edge list delivers this round — ``edge_mask & frontier[src]``
    summed per shard, exactly the population ``relax`` counts (so the
    vector sums to the round's kernel-side message count).  Host-side
    numpy over the (S, E_max) partition arrays; feeds the flight
    recorder's per-shard skew/balance gauge (the "message balance across
    workers" axis of the distributed-graph-systems evaluation
    literature)."""
    import numpy as np

    mask = np.asarray(edge_mask)
    srcs = np.asarray(edge_src_root_flat)
    g = np.asarray(gchg).reshape(-1)
    return (mask & g[srcs]).sum(axis=tuple(range(1, mask.ndim)))


def expected_round_messages(edge_mask, edge_src_root_flat, gchg,
                            laned: bool = False) -> int:
    """The exact message total a clean round on frontier ``gchg`` must
    report — ``shard_message_mirror`` summed over shards.  This is the
    resilient driver's inbox-integrity detector: a dispatched round whose
    reported count falls short (a dropped inbox) or overshoots (a
    duplicated inbox) of this host mirror raises a typed
    ``FaultDetected`` instead of silently converging to a wrong-work
    fixpoint.  With ``laned=True`` the trailing axis of ``gchg`` is the
    query-lane axis Q and the expectation sums over lanes, matching the
    laned ``relax`` population count."""
    import numpy as np

    g = np.asarray(gchg)
    if not laned:
        return int(shard_message_mirror(
            edge_mask, edge_src_root_flat, g).sum())
    gq = g.reshape(-1, g.shape[-1])
    return int(sum(
        shard_message_mirror(edge_mask, edge_src_root_flat,
                             gq[:, q]).sum()
        for q in range(gq.shape[1])))


def mask_shard_frontier(chg, shard: int):
    """Frontier ``chg`` ((S, R_max[, Q])) with shard ``shard``'s rows
    forced False — the chaos injector's 'dropped inbox': that shard's
    outgoing messages silently vanish for one round.  Returns a new
    array; the caller keeps the untampered original for retry."""
    return chg.at[shard].set(False) if hasattr(chg, "at") else _mask_np(
        chg, shard)


def _mask_np(chg, shard: int):
    import numpy as np

    out = np.array(chg, copy=True)
    out[shard] = False
    return out
