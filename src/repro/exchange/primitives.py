"""Lane-generic relax / exchange / collapse primitives.

Every function here accepts value and frontier tables either **unlaned**
(``(V,)`` — one query, the classic engine layout) or **laned** (``(V, Q)``
— a trailing query-lane axis, one column per concurrent query) and picks
the matching kernel / jnp form.  The lane axis is detected from rank, so
the round compositions in ``exchange.rounds`` are written once.

The arrays argument is duck-typed against ``core.engine.DeviceArrays``
(the static per-shard partition tables); ``cfg`` against
``core.engine.EngineConfig`` — this module must not import ``core.engine``
(the engine imports *us*).

The relax, the exchange and the collapse each run under a
``jax.named_scope`` (``relax``, ``exchange``, ``collapse``), so their
device operations carry that name in their ``op_name`` metadata; in a
profile, the innermost of the three names an operation's layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.actions import Semiring


def reduce_axis0(sem: Semiring, x):
    """Semiring reduction over axis 0 (trailing axes — incl. Q — ride)."""
    return jnp.min(x, axis=0) if sem.segment == "min" else jnp.sum(x, axis=0)


def _identity(sem: Semiring, dtype):
    return jnp.asarray(sem.identity, dtype)


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return call
    return wrap


# --------------------------------------------------------------------------
# relax phase: gather frontier sources, build messages, partial-reduce
# --------------------------------------------------------------------------

@scoped("relax")
def relax(sem: Semiring, cfg, edge_src, edge_w, edge_mask, ids, gval, gchg,
          num_segments: int, lane_unitw=None, worklist=None):
    """Relax phase over one edge set (flattened internally).

    ``gval``/``gchg``: (V,) or (V, Q).  Returns ((num_segments[, Q])
    partial, message count — scalar unlaned, (Q,) per-lane laned).

    Laned 'add_w' honors ``lane_unitw``: lanes with a nonzero flag relax
    with the constant weight 1.0 (BFS levels inside an SSSP launch).

    ``worklist`` — a host-planned live-cell launch (see
    ``kernels.fused_relax_reduce.WorklistPlanner``) — swaps the fused
    kernel's dense early-exit grid for the 1-D sparse launch; it only
    applies to the fused Pallas path (the jnp oracle has no grid) and is
    built per round by the host-driven engine loops
    (``EngineConfig.grid_mode``).  With ``cfg.grid_mode=
    'device_worklist'`` (and no explicit plan) the live-cell list is
    compacted ON DEVICE instead — fully traced, so the same round
    composes into `lax.while_loop` / `shard_map` fixpoints with zero
    host syncs.
    """
    laned = gval.ndim == 2
    src = edge_src.reshape(-1)
    idsf = ids.reshape(-1)
    w = edge_w.reshape(-1)
    mask = edge_mask.reshape(-1)
    # only the device mode is forwarded to the kernel dispatch: host
    # modes ('worklist'/'auto') arrive as a pre-planned worklist= (or
    # keep the dense grid on rounds the planner declined)
    grid_mode = ("device_worklist"
                 if (worklist is None
                     and getattr(cfg, "grid_mode", "dense")
                     == "device_worklist")
                 else "dense")

    if not laned:
        if cfg.use_pallas and cfg.pallas_mode == "fused":
            if sem.relax_kind is None:
                raise ValueError(
                    f"semiring {sem.name!r} has no kernel relax form "
                    "(relax_kind=None); construct it from actions.RELAX_FNS "
                    "or run with use_pallas=False")
            from repro.kernels import ops as kops
            # the Fig-6 message count rides along for free: it is a
            # reduction of the same frontier mask that builds the
            # kernel's chunk bitmap
            partial, count = kops.fused_relax_reduce(
                gval, gchg, src, w, mask, idsf, num_segments,
                relax_kind=sem.relax_kind, kind=sem.segment,
                worklist=worklist,
                smem_budget_bytes=getattr(cfg, "smem_budget_bytes", None),
                grid_mode=grid_mode)
            if not cfg.track_stats:
                count = jnp.zeros((), jnp.int32)
            return partial, count
        src_val = jnp.take(gval, edge_src, axis=0)
        active = edge_mask & jnp.take(gchg, edge_src, axis=0)
        msg = jnp.where(active, sem.relax(src_val, edge_w),
                        _identity(sem, src_val.dtype))
        if cfg.use_pallas:  # 'reduce': XLA relax ops + Pallas segment reduce
            from repro.kernels import ops as kops
            partial = kops.segment_combine(
                msg.reshape(-1), idsf, num_segments, kind=sem.segment)
        else:
            partial = sem.segment_combine(msg.reshape(-1), idsf, num_segments)
        count = active.sum() if cfg.track_stats else jnp.zeros((), jnp.int32)
        return partial, count

    # --- laned: (V, Q) tables over one shared edge structure ---
    q = gval.shape[-1]
    if sem.relax_kind not in ("add_w", "mul_w"):
        raise ValueError(
            f"laned relax supports relax_kind 'add_w'|'mul_w', got "
            f"{sem.relax_kind!r} (express BFS lanes with lane_unitw=1)")
    unitw = (jnp.zeros((q,), jnp.int32) if lane_unitw is None
             else jnp.asarray(lane_unitw, jnp.int32).reshape(q))
    if cfg.use_pallas:
        if cfg.pallas_mode != "fused":
            raise ValueError(
                "laned Pallas execution is fused-only (the pre-fusion "
                "'reduce' composition has no laned form)")
        from repro.kernels import ops as kops
        partial, counts = kops.fused_relax_reduce_lanes(
            gval, gchg, unitw, src, w, mask, idsf, num_segments,
            relax_kind=sem.relax_kind, kind=sem.segment,
            worklist=worklist,
            smem_budget_bytes=getattr(cfg, "smem_budget_bytes", None),
            grid_mode=grid_mode)
        if not cfg.track_stats:
            counts = jnp.zeros((q,), jnp.int32)
        return partial, counts
    src_val = jnp.take(gval, src, axis=0)                    # (E, Q)
    active = mask[:, None] & jnp.take(gchg, src, axis=0)
    if sem.relax_kind == "add_w":
        w_eff = jnp.where(unitw[None, :] > 0,
                          jnp.asarray(1.0, w.dtype), w[:, None])
        msg = src_val + w_eff
    else:                                                    # 'mul_w'
        msg = src_val * w[:, None]
    msg = jnp.where(active, msg, _identity(sem, msg.dtype))
    init = jnp.full((num_segments, q), sem.identity, msg.dtype)
    partial = (init.at[idsf].min(msg) if sem.segment == "min"
               else init.at[idsf].add(msg))
    counts = (active.sum(axis=0, dtype=jnp.int32) if cfg.track_stats
              else jnp.zeros((q,), jnp.int32))
    return partial, counts


# --------------------------------------------------------------------------
# stacked relax compositions (all shards resident on one device)
# --------------------------------------------------------------------------

@scoped("exchange")
def stacked_dense_inbox(sem: Semiring, arrays, cfg, gval, gchg, total: int,
                        lane_unitw=None, worklist=None):
    """Stacked dense relax: the reduced (total[, Q]) global inbox + count.

    Fused path: all shards' edges address the same global slot space, so
    the whole stack collapses in ONE kernel launch (the kernel's in-place
    block accumulation replaces the (S, total) partial + axis-0 reduce)."""
    if cfg.use_pallas and cfg.pallas_mode == "fused":
        return relax(sem, cfg, arrays.edge_src_root_flat, arrays.edge_w,
                     arrays.edge_mask, arrays.edge_dst_flat, gval, gchg,
                     total, lane_unitw, worklist=worklist)
    partial, counts = jax.vmap(
        lambda s, w, m, i: relax(sem, cfg, s, w, m, i, gval, gchg, total,
                                 lane_unitw)
    )(arrays.edge_src_root_flat, arrays.edge_w, arrays.edge_mask,
      arrays.edge_dst_flat)
    return reduce_axis0(sem, partial), counts.sum(axis=0)


@scoped("exchange")
def stacked_compact_partial(sem: Semiring, arrays, cfg, S: int, P_t: int,
                            gval, gchg, lane_unitw=None, worklist=None):
    """Stacked compact relax: (S_src, S_tgt, P_t[, Q]) partials + count.

    Fused path: source shards get disjoint id windows of width S*P_t, so
    one kernel launch over the flattened edge stack produces every
    per-source partial (compact slot meaning depends on the source shard,
    hence the offsets — contributions must NOT merge across sources)."""
    if cfg.use_pallas and cfg.pallas_mode == "fused":
        offs = (jnp.arange(S, dtype=jnp.int32) * (S * P_t))[:, None]
        ids = arrays.edge_dst_compact + offs
        flat, count = relax(sem, cfg, arrays.edge_src_root_flat,
                            arrays.edge_w, arrays.edge_mask, ids, gval,
                            gchg, S * S * P_t, lane_unitw,
                            worklist=worklist)
        return flat.reshape((S, S, P_t) + flat.shape[1:]), count
    partial, counts = jax.vmap(
        lambda s, w, m, i: relax(sem, cfg, s, w, m, i, gval, gchg,
                                 S * P_t, lane_unitw)
    )(arrays.edge_src_root_flat, arrays.edge_w, arrays.edge_mask,
      arrays.edge_dst_compact)
    return partial.reshape((S, S, P_t) + partial.shape[2:]), \
        counts.sum(axis=0)


# --------------------------------------------------------------------------
# inbox scatter + rhizome collapse
# --------------------------------------------------------------------------

@scoped("exchange")
def scatter_inbox(sem: Semiring, recv_t, slot_map_t, R_max: int):
    """recv_t: (S_src, P_t[, Q]) contributions; slot_map_t: (S_src, P_t)
    local slots (R_max = pad).  Scatter-combine into (R_max[, Q])."""
    tail = recv_t.shape[slot_map_t.ndim:]
    init = jnp.full((R_max + 1,) + tail, sem.identity, recv_t.dtype)
    flat = recv_t.reshape((-1,) + tail)
    idx = slot_map_t.reshape(-1)
    out = (init.at[idx].min(flat) if sem.segment == "min"
           else init.at[idx].add(flat))
    return out[:R_max]


@scoped("collapse")
def collapse(sem: Semiring, gx, sibling_flat, sibling_mask):
    """Rhizome collapse: AND-gate over all replicas of each slot's vertex.

    ``gx``: (V,) or (V, Q) gathered table; sibling tables index the
    leading axis (the lane axis rides along).  Returns the sibling-
    combined table shaped like ``sibling_flat`` (+ Q)."""
    return _sibling_reduce(sem, gx, sibling_flat, sibling_mask)


def _sibling_reduce(sem: Semiring, g, sib_idx, sib_mask):
    """Semiring-combine each slot's K siblings: ``sib_idx``/``sib_mask``
    are (..., K) tables into the leading axis of ``g`` ((V,) or (V, Q)).
    The gather runs K-major: a large gather whose minor dim is K (the
    rhizome fan-out, a handful) takes XLA's TPU compiler minutes at
    scale-20 sizes, against seconds with K leading."""
    sib = jnp.take(g, jnp.moveaxis(sib_idx, -1, 0), axis=0)  # (K, ...[, Q])
    mask = jnp.moveaxis(sib_mask, -1, 0)
    if g.ndim == 2:
        mask = mask[..., None]
    sib = jnp.where(mask, sib, _identity(sem, sib.dtype))
    return (jnp.min(sib, axis=0) if sem.segment == "min"
            else jnp.sum(sib, axis=0))


@scoped("collapse")
def compact_collapse(sem: Semiring, cand, rz_local, rz_sib_idx, rz_sib_mask,
                     gather_fn, R_max: int, R_rz_max: int):
    """Collapse only rhizome slots: compact-gather them, all-gather the
    small table, combine siblings, scatter back.  ``cand``:
    (..., R_max[, Q]).  min semirings min-set (collapsed ≼ cand under the
    semiring order, so ``cand`` may be any combined candidate); sum
    semirings overwrite each rhizome slot with the sibling total (each
    sibling's own partial is included in the sum, so set — never add —
    keeps it exact), which requires ``cand`` to be bare inbox partials —
    summing combined val+inbox candidates would double-count every
    sibling's val (hence the min-only fixpoint runners; only the
    PageRank/PPR rounds pass sum semirings here)."""
    laned = cand.ndim == rz_local.ndim + 1
    slot_axis = -2 if laned else -1
    pad_shape = list(cand.shape)
    pad_shape[slot_axis] = 1
    cand_pad = jnp.concatenate(
        [cand, jnp.full(pad_shape, sem.identity, cand.dtype)],
        axis=slot_axis)
    rz_idx = rz_local[..., None] if laned else rz_local
    compact = jnp.take_along_axis(cand_pad, rz_idx, axis=slot_axis)
    g = gather_fn(compact)                       # (S*R_rz_max[, Q]) flat
    collapsed = _sibling_reduce(sem, g, rz_sib_idx, rz_sib_mask)
    idx = tuple(jnp.indices(rz_local.shape)[:-1]) + (rz_local,)
    if sem.segment == "min":
        upd = cand_pad.at[idx].min(collapsed)
    else:
        upd = cand_pad.at[idx].set(collapsed)
    return upd[..., :R_max, :] if laned else upd[..., :R_max]


# --------------------------------------------------------------------------
# exchange-volume accounting (the §Perf message-reduction metric)
# --------------------------------------------------------------------------

def exchange_volume(S: int, R_max: int, P_t: int, cfg) -> int:
    """Entries that transit the inter-shard exchange per round, per live
    lane: every shard ships its per-target partial — (S, R_max) rows of
    the dense global inbox, or (S, P_t) targeted (target, distinct-slot)
    compact tables.  The compact win is exactly the paper's message
    reduction: P_t < R_max whenever shards feed only a subset of each
    target's slots (always, on skewed partitions).  On the stacked path
    no collective runs, but the exchanged tensors are the same size, so
    the same accounting holds; a converged lane is excluded by the caller
    (its column is all identity — it adds no message volume)."""
    width = P_t if cfg.exchange == "compact" else R_max
    return S * S * width
