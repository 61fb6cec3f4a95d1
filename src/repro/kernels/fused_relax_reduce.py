"""Pallas TPU kernel: frontier-aware relax + blocked segment reduce.

One engine round is

    active = edge_mask & gchg[edge_src]       # frontier mask
    msg    = where(active, relax(gval[edge_src], w), identity)
    inbox  = segment_reduce(msg, edge_dst)

The launchers run the first two lines as ONE XLA fusion (frontier
gather + semiring relax + mask -> the per-edge message array) and the
kernel runs the reduction with the frontier skips below.  The gather
stays in XLA on purpose: Mosaic has no general in-kernel dynamic gather,
and XLA's TPU gather reads the value table straight out of HBM, so the
table's size never meets a VMEM budget.

Blocking follows ``rhizome_segment_reduce``: edges ride lane-dense as
``(E_pad / 128, 128)`` rows cut into ``EBLK``-edge chunks (one
``(8, 128)`` block), the segment axis into ``SBLK`` blocks; cell (i, j)
folds the chunk's messages into block *i*'s contribution with VPU
min / add (``block_contrib``).

Two levels of scalar-prefetched grid-cell skipping (the TPU form of the
paper's diffusion pruning — work stays proportional to the frontier):

1. **Sorted-range skip** — edges are sorted by destination, so chunk *j*
   covers segment ids ``[chunk_lo[j], chunk_hi[j]]``; cells whose segment
   block does not intersect are skipped (static sparsity of the CSR sort).
2. **Frontier chunk skip** — ``chunk_act[j]`` records whether ANY edge
   in chunk *j* has a changed (diffusing) source this round.  On late
   BFS/SSSP rounds the frontier is a tiny fraction of the graph, so most
   chunks are dead and their grid cells are skipped *entirely* across all
   segment blocks — the paper's "stale diffusions are subsumed" pruning,
   realized as predicated grid cells.

``fused_grid_cells`` mirrors the two skip predicates on the host so
benchmarks/tests can count exactly how many grid cells execute; the
kernels' optional ``with_debug`` counter reports the *kernel-side*
executed-cell total so the mirror is provably exact
(``tests/test_fused_kernel.py::test_grid_cell_dma_oracle_*``).

Semiring relax is selected statically via ``relax_kind``
(``Semiring.relax_kind``, single-sourced with the jnp path through
``actions.RELAX_FNS``): 'add_w' (min-plus / SSSP), 'add_one' (BFS level
relax; the weight is ignored), 'mul_w' (plus-times / PageRank).  Min
semirings are bit-identical to ``ref.fused_relax_reduce_ref``; sum
differs only by float reassociation.

**Lanes.**  The laned kernels serve Q concurrent queries over one edge
structure.  Their messages ride transposed, ``(q_pad, E_pad)``, with the
query axis on the sublanes (``q_pad`` a multiple of ``LANE_PAD``, the
sublane tile — tail lanes are frontier-dead and masked, so padding never
changes results); each cell loops over the lanes.

**Sparsity-proportional worklist launches.**  The dense grid launches
every ``(num_sblk, num_chunks)`` cell and early-exits the dead ones —
launch cost stays proportional to *total* work even when the frontier is
four cells wide.  Every kernel therefore has a **worklist twin**: a
host-built (``WorklistPlanner`` / ``plan_worklist``) scalar-prefetched
list of live ``(i, j)`` cell pairs, launched as a 1-D grid over the
power-of-two-padded live count.  Each worklist cell writes its own
partial block (no out-block revisiting — revisit order under a sparse
worklist is non-consecutive, which Pallas out pipelining does not
guarantee), and a scatter-combine folds the partials into the inbox;
padded cells emit the combine identity, so the scatter is exact.
Bit-identical to the dense grid for min semirings; sum differs only by
scatter reassociation.  The planner's per-cell dst filter drops cells
whose chunk has no frontier-active edge landing in the cell's block.

Scalar-prefetch tables live in SMEM; ``smem_table_bytes`` prices them
and launches warn when a configured ``smem_budget_bytes`` is exceeded.
"""
from __future__ import annotations

import functools
import typing
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.actions import RELAX_FNS
from repro.kernels.rhizome_segment_reduce import (
    EBLK, LANES, ROWS, SBLK, block_contrib, combine, edge_rows, identity_of,
)
from repro.kernels.rhizome_segment_reduce import round_up as _round_up

LANE_PAD = 8     # laned launches pad Q to the sublane tile: tail lanes are
                 # frontier-dead identity rows

WL_PAD = 8      # worklist launches are padded to >= this many cells and
                # then to a power of two, so jit retraces O(log cells)
                # times per partition instead of once per distinct count

WL_LAUNCH_CELLS = 32768   # worklist cells per pallas_call: the call's
                          # wl_i/wl_j prefetch tables (8 bytes a cell) must
                          # fit SMEM, 1 MiB on a v5e core

RELAX_KINDS = tuple(RELAX_FNS)

# pairings for which the combine identity absorbs under relax —
# relax(identity, w) == identity — the property the frontier masking
# relies on (an inactive source must never contribute)
ABSORBING_PAIRS = frozenset(
    {("add_w", "min"), ("add_one", "min"), ("mul_w", "sum")})

__all__ = [
    "EBLK", "SBLK", "LANE_PAD", "WL_PAD", "Worklist", "WorklistInfo",
    "WorklistPlanner", "build_device_worklist", "device_worklist_pad",
    "fused_grid_cells", "fused_relax_reduce_lanes_pallas",
    "fused_relax_reduce_pallas", "plan_worklist", "smem_table_bytes",
]


def _check_pair(relax_kind: str, kind: str):
    assert relax_kind in RELAX_KINDS, relax_kind
    if (relax_kind, kind) not in ABSORBING_PAIRS:
        raise ValueError(
            f"non-absorbing relax/combine pairing {(relax_kind, kind)}: "
            "frontier masking requires relax(identity, w) == identity "
            f"(supported: {sorted(ABSORBING_PAIRS)})")


def _lane_pad(q: int, lane_tile=None) -> int:
    """Padded lane count: a multiple of ``LANE_PAD`` (the sublane tile the
    transposed message layout needs) or of an explicit ``lane_tile``."""
    return _round_up(q, lane_tile or LANE_PAD)


def smem_table_bytes(n_chunks: int, wl_cells: int = 0) -> int:
    """Byte footprint of the scalar-prefetch tables one fused pallas_call
    pins in SMEM: the dense grid's per-chunk ``chunk_lo/hi/act`` rows, or
    a worklist call's per-cell ``wl_i/wl_j`` pairs plus ``nlive``
    (``wl_cells`` is the padded worklist length, launched in slices of at
    most ``WL_LAUNCH_CELLS``; 0 = dense grid).  All int32."""
    rows = (2 * min(wl_cells, WL_LAUNCH_CELLS) + 1 if wl_cells
            else 3 * n_chunks)
    return rows * 4


def _warn_smem(n_chunks: int, wl_cells: int, smem_budget_bytes):
    if smem_budget_bytes is None:
        return
    need = smem_table_bytes(n_chunks, wl_cells)
    if need > smem_budget_bytes:
        warnings.warn(
            f"fused-kernel scalar-prefetch tables ({n_chunks} chunks, "
            f"wl_cells={wl_cells}) weigh {need} bytes — over "
            f"smem_budget_bytes={smem_budget_bytes}; the tables belong in "
            "an HBM side table (ROADMAP)", stacklevel=3)


# --------------------------------------------------------------------------
# per-edge messages (one XLA fusion) and the scalar-prefetch tables
# --------------------------------------------------------------------------

def _edge_messages(gval, gchg, edge_src, edge_w, edge_mask, edge_dst,
                   relax_kind, kind, e_pad):
    """Frontier gather + relax + mask, padded to ``e_pad`` edges.  Returns
    (ids, msg, valid, active) — padding edges carry id 0, the identity
    message and valid = active = False."""
    e = edge_src.shape[0]
    identity = identity_of(kind)
    active = edge_mask & jnp.take(gchg, edge_src)
    msg = RELAX_FNS[relax_kind](jnp.take(gval, edge_src), edge_w)
    msg = jnp.where(active, msg, identity).astype(jnp.float32)
    pad = (0, e_pad - e)
    return (jnp.pad(edge_dst.astype(jnp.int32), pad),
            jnp.pad(msg, pad, constant_values=identity),
            jnp.pad(edge_mask, pad), jnp.pad(active, pad))


def _lane_messages(gval, gchg, lane_unitw, edge_src, edge_w, edge_mask,
                   edge_dst, relax_kind, kind, e_pad, q_pad):
    """Laned twin of ``_edge_messages``: (V, Q) tables -> transposed
    (q_pad, e_pad) messages (tail lanes and padding edges hold the
    identity), plus ids, validity, the (e_pad,) OR-across-lanes activity
    and the (Q,) per-lane active-edge counts."""
    e = edge_src.shape[0]
    q = gval.shape[1]
    identity = identity_of(kind)
    active = edge_mask[None, :] & jnp.take(gchg.T, edge_src, axis=1)
    sval = jnp.take(gval.T, edge_src, axis=1)                  # (Q, E)
    if relax_kind == "add_w":
        unitw = jnp.asarray(lane_unitw, jnp.int32).reshape(q, 1)
        msg = sval + jnp.where(unitw > 0, jnp.asarray(1.0, edge_w.dtype),
                               edge_w[None, :])
    else:                                                      # 'mul_w'
        msg = sval * edge_w[None, :]
    msg = jnp.where(active, msg, identity).astype(jnp.float32)
    pad = (0, e_pad - e)
    msg = jnp.pad(msg, ((0, q_pad - q), pad), constant_values=identity)
    counts = active.sum(axis=1, dtype=jnp.int32)
    return (jnp.pad(edge_dst.astype(jnp.int32), pad), msg,
            jnp.pad(edge_mask, pad), jnp.pad(active.any(axis=0), pad),
            counts)


def _chunk_tables(ids_p, valid, active):
    """Scalar-prefetch tables: per-chunk [lo, hi] id range of the valid
    edges + the any-frontier-active bit."""
    n_chunks = ids_p.shape[0] // EBLK
    idc = ids_p.reshape(n_chunks, EBLK)
    valid = valid.reshape(n_chunks, EBLK)
    chunk_lo = jnp.where(valid, idc, jnp.iinfo(jnp.int32).max).min(axis=1)
    chunk_hi = jnp.where(valid, idc, -1).max(axis=1)
    chunk_act = active.reshape(n_chunks, EBLK).any(axis=1)
    return chunk_lo, chunk_hi, chunk_act.astype(jnp.int32)


# --------------------------------------------------------------------------
# kernel bodies
# --------------------------------------------------------------------------

def _bump_dbg(dbg, first):
    """Executed-cell counter (the ``with_debug`` output): zeroed at the
    first grid step (the grid runs sequentially), bumped per live cell."""
    if not dbg:
        return lambda: None
    dbg_ref, = dbg

    @pl.when(first)
    def _zero():
        dbg_ref[0] = 0

    def bump():
        dbg_ref[0] += 1
    return bump


def _cell(ids_ref, msg_ref, out_ref, seg0, kind, laned):
    """Combine one live cell's contribution into ``out_ref``: (1, SBLK)
    unlaned; (q_pad, SBLK) laned, one row per query lane."""
    if not laned:
        contrib = block_contrib(ids_ref, lambda r: msg_ref[r:r + 1, :],
                                seg0, kind)
        out_ref[...] = combine(kind, out_ref[...], contrib)
        return

    # Mosaic loads and stores sublane-aligned groups only, so lanes go
    # LANE_PAD at a time: one aligned (LANE_PAD, ...) window per group,
    # static row picks inside it, and the group's rows reassembled by
    # sublane select before one aligned store
    row_id = jax.lax.broadcasted_iota(jnp.int32, (LANE_PAD, SBLK), 0)

    def group(g, carry):
        rows = pl.ds(pl.multiple_of(g * LANE_PAD, LANE_PAD), LANE_PAD)
        block = out_ref[rows, :]
        for k in range(LANE_PAD):
            contrib = block_contrib(
                ids_ref,
                lambda r: msg_ref[rows, pl.ds(r * LANES, LANES)][k:k + 1, :],
                seg0, kind)
            block = jnp.where(row_id == k, combine(kind, block, contrib),
                              block)
        out_ref[rows, :] = block
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0] // LANE_PAD, group, 0)


def _kernel_dense(chunk_lo_ref, chunk_hi_ref, chunk_act_ref, ids_ref,
                  msg_ref, out_ref, *dbg, kind, laned):
    i = pl.program_id(0)  # segment block
    j = pl.program_id(1)  # edge chunk
    bump = _bump_dbg(dbg, (i == 0) & (j == 0))

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, identity_of(kind),
                                out_ref.dtype)

    seg0 = i * SBLK
    # level 1: sorted-edges range skip — chunk j covers [chunk_lo, chunk_hi]
    intersects = (chunk_hi_ref[j] >= seg0) & (chunk_lo_ref[j] < seg0 + SBLK)
    # level 2: frontier skip — any changed source in this edge chunk?
    live = intersects & (chunk_act_ref[j] > 0)

    @pl.when(live)
    def _compute():
        _cell(ids_ref, msg_ref, out_ref, seg0, kind, laned)
        bump()


def _kernel_wl(wl_i_ref, wl_j_ref, nlive_ref, ids_ref, msg_ref, out_ref,
               *dbg, kind, laned):
    """Worklist cell ``c``: edge chunk ``wl_j[c]`` against segment block
    ``wl_i[c]``, into its own partial block."""
    c = pl.program_id(0)
    bump = _bump_dbg(dbg, c == 0)
    out_ref[...] = jnp.full(out_ref.shape, identity_of(kind), out_ref.dtype)

    @pl.when(c < nlive_ref[0])
    def _compute():
        _cell(ids_ref, msg_ref, out_ref, wl_i_ref[c] * SBLK, kind, laned)
        bump()


_DBG_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)
_DBG_SHAPE = jax.ShapeDtypeStruct((1,), jnp.int32)


def _launch(kernel, scalars, ids_p, msg, grid, edge_map, out_block,
            out_map, out_rows, interpret, with_debug):
    """One pallas_call over lane-dense edge rows; ``msg`` is (e_pad,)
    unlaned or (q_pad, e_pad) laned.  Returns (out[, dbg])."""
    if msg.ndim == 1:
        msg_spec = pl.BlockSpec((ROWS, LANES), edge_map)
        msg = edge_rows(msg)
    else:
        msg_spec = pl.BlockSpec(
            (msg.shape[0], EBLK),
            lambda *a: (0, edge_map(*a)[0]))
    out_spec = pl.BlockSpec((pl.Squeezed(),) + out_block, out_map)
    out_shape = jax.ShapeDtypeStruct((out_rows,) + out_block, jnp.float32)
    if with_debug:
        out_spec = [out_spec, _DBG_SPEC]
        out_shape = [out_shape, _DBG_SHAPE]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=[pl.BlockSpec((ROWS, LANES), edge_map), msg_spec],
            out_specs=out_spec,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(*scalars, edge_rows(ids_p), msg)


def _launch_dense(ids_p, msg, valid, active, num_segments, kind, laned,
                  interpret, with_debug):
    """Dense (n_sblk, n_chunks) grid; returns the blocked (n_i, rows, SBLK)
    inbox (rows = 1 or q_pad) [+ dbg]."""
    n_i = _round_up(num_segments, SBLK) // SBLK
    tables = _chunk_tables(ids_p, valid, active)
    rows = msg.shape[0] if laned else 1
    return _launch(
        functools.partial(_kernel_dense, kind=kind, laned=laned), tables,
        ids_p, msg, (n_i, ids_p.shape[0] // EBLK),
        lambda i, j, *_: (j, 0), (rows, SBLK), lambda i, j, *_: (i, 0, 0),
        n_i, interpret, with_debug)


def _launch_wl(ids_p, msg, wl_i, wl_j, nlive, num_segments, kind, laned,
               interpret, with_debug):
    """1-D worklist grid; each cell writes its own partial, scatter-
    combined by ``wl_i`` into the blocked (n_i, rows, SBLK) inbox (dead
    and padded cells hold the identity, so every row scatters exactly)."""
    n_i = _round_up(num_segments, SBLK) // SBLK
    rows = msg.shape[0] if laned else 1
    # one pallas_call per WL_LAUNCH_CELLS slice keeps every call's
    # scalar-prefetched cell list within SMEM; l_pad and the slice are
    # powers of two, so the slices tile the list exactly
    step = min(wl_i.shape[0], WL_LAUNCH_CELLS)
    parts, dbgs = [], []
    for start in range(0, wl_i.shape[0], step):
        cells = slice(start, start + step)
        raw = _launch(
            functools.partial(_kernel_wl, kind=kind, laned=laned),
            (wl_i[cells], wl_j[cells], jnp.clip(nlive - start, 0, step)),
            ids_p, msg, (step,), lambda c, wi, wj, nl: (wj[c], 0),
            (rows, SBLK), lambda c, *_: (c, 0, 0), step, interpret,
            with_debug)
        part, dbg = raw if with_debug else (raw, None)
        parts.append(part)
        dbgs.append(dbg)
    partials = jnp.concatenate(parts)
    dbg = sum(dbgs[1:], dbgs[0]) if with_debug else None
    init = jnp.full((n_i, rows, SBLK), identity_of(kind), jnp.float32)
    folded = (init.at[wl_i].min(partials) if kind == "min"
              else init.at[wl_i].add(partials))
    return (folded, dbg) if with_debug else folded


def _reduce_launch(ids_p, msg, valid, active, num_segments, kind, laned,
                   interpret, with_debug, grid_mode, worklist):
    """Dispatch one launch by grid mode.  ``device_worklist`` compacts the
    live-cell list on device; when the live count outgrows the static
    list length (possible only for edges not sorted by destination) the
    dense grid runs instead, so no cell is ever dropped."""
    dense = functools.partial(
        _launch_dense, ids_p, msg, valid, active, num_segments, kind, laned,
        interpret, with_debug)
    if worklist is None and grid_mode == "device_worklist":
        tables = _chunk_tables(ids_p, valid, active)
        l_pad = device_worklist_pad(ids_p.shape[0], num_segments)
        wl_i, wl_j, nlive = _compact_live_cells(
            *tables, _round_up(num_segments, SBLK) // SBLK, l_pad)
        return jax.lax.cond(
            nlive[0] <= l_pad,
            lambda: _launch_wl(ids_p, msg, wl_i, wl_j, nlive, num_segments,
                               kind, laned, interpret, with_debug),
            dense)
    if worklist is not None:
        return _launch_wl(ids_p, msg, jnp.asarray(worklist.wl_i),
                          jnp.asarray(worklist.wl_j),
                          jnp.asarray(worklist.nlive), num_segments, kind,
                          laned, interpret, with_debug)
    return dense()


def _finish(raw, with_debug, unblock, count, with_count):
    out, dbg = raw if with_debug else (raw, None)
    res = (unblock(out),)
    if with_count:
        res += (count,)
    if with_debug:
        res += (dbg,)
    return res[0] if len(res) == 1 else res


# --------------------------------------------------------------------------
# worklist planning (host side)
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class Worklist:
    """A planned sparse launch: the live (i, j) cell list.

    Registered as a pytree so drivers can pass a fresh per-round plan
    through one jitted round function — the arrays are leaves (jit
    retraces only when the padded length bucket changes).  ``nlive``
    rides as a (1,) int32 array for the same reason."""

    def __init__(self, wl_i, wl_j, nlive):
        self.wl_i = wl_i
        self.wl_j = wl_j
        self.nlive = nlive

    @property
    def l_pad(self) -> int:
        return self.wl_i.shape[0]

    def tree_flatten(self):
        return (self.wl_i, self.wl_j, self.nlive), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class WorklistInfo(typing.NamedTuple):
    """Host-side accounting of one plan (the ``fused_grid_cells`` mirror
    for worklist launches; never crosses into jit)."""

    cells: int           # live cells after the dst-range empty-cell drop
    launched: int        # padded 1-D grid length
    dense_live: int      # what the dense grid's two-level skip would run
    smem_table_bytes: int


def _wl_pad_len(nlive: int, pad_to: int = WL_PAD) -> int:
    return max(pad_to, 1 << max(nlive - 1, 0).bit_length())


class WorklistPlanner:
    """Precomputes the frontier-independent parts of worklist planning
    for one launch shape (one edge set + segment count), so per-round
    plans only pay the frontier-dependent work.

    ``edge_dst``/``edge_mask``/``edge_src`` may be (S, E_max) stacked or
    flat — flattened exactly as the kernels flatten them.  Each chunk's
    intersecting segment blocks are the contiguous range
    ``[lo // SBLK, hi // SBLK]`` of its valid ids, so planning works on
    those ranges and never builds the (n_sblk, n_chunks) grid.
    ``plan(gchg)`` returns (Worklist, WorklistInfo); for laned launches
    pass the OR-across-lanes frontier."""

    def __init__(self, edge_dst, edge_mask, edge_src, num_segments: int,
                 *, smem_budget_bytes: int | None = None):
        ids = np.asarray(edge_dst).reshape(-1)
        mask = np.asarray(edge_mask).reshape(-1)
        srcs = np.asarray(edge_src).reshape(-1)
        e = ids.shape[0]
        e_pad = _round_up(e, EBLK)
        self.num_segments = int(num_segments)
        self.n_i = _round_up(num_segments, SBLK) // SBLK
        self.n_chunks = e_pad // EBLK
        self.smem_budget_bytes = smem_budget_bytes
        self._smem_warned = False

        def chunked(x, dtype):
            out = np.zeros(e_pad, dtype)
            out[:e] = x
            return out.reshape(self.n_chunks, EBLK)

        ids_c = chunked(ids, np.int64)
        self.mask = chunked(mask, bool)
        self.srcs = chunked(srcs, np.int64)
        lo = np.where(self.mask, ids_c, np.iinfo(np.int64).max).min(axis=1)
        hi = np.where(self.mask, ids_c, -1).max(axis=1)
        has = self.mask.any(axis=1)
        self.lo_blk = np.where(has, lo // SBLK, 0)
        self.span = np.where(has, hi // SBLK - self.lo_blk + 1, 0)
        self.blk_of = ids_c // SBLK              # dst block of each edge

    @property
    def total_cells(self) -> int:
        return self.n_i * self.n_chunks

    def _active(self, gchg):
        gchg = np.asarray(gchg).reshape(-1)
        act = self.mask & gchg[self.srcs]        # (n_chunks, EBLK)
        return act, act.any(axis=1)

    def _cells(self, live_chunk):
        """j-major (chunk, block) pairs of the dense grid's live cells."""
        jj = np.nonzero(live_chunk)[0]
        span = self.span[jj]
        jj = np.repeat(jj, span)
        start = np.repeat(np.cumsum(span) - span, span)
        ii = self.lo_blk[jj] + (np.arange(jj.shape[0]) - start)
        return jj, ii

    def live_fraction(self, gchg) -> float:
        """Fraction of the dense grid the two-level skip would execute —
        the signal ``grid_mode='auto'`` keys the dense/worklist choice on."""
        _, live_chunk = self._active(gchg)
        return int(self.span[live_chunk].sum()) / max(self.total_cells, 1)

    def dense_mirror(self, gchg) -> dict:
        """Mirror of the DENSE grid's launch for this planner's edge set:
        live cells under the two-level skip, matching ``fused_grid_cells``'s
        ``fused_live`` exactly.  The flight recorder uses this for rounds
        that kept the dense grid (grid_mode='dense', or 'auto' above the
        live-fraction threshold)."""
        _, live_chunk = self._active(gchg)
        return {"cells": int(self.span[live_chunk].sum()),
                "launched": self.total_cells}

    def plan(self, gchg, pad_to: int = WL_PAD, dst_filter: bool = True,
             max_live_fraction: float | None = None):
        """Plan one round's launch from the (V,) bool frontier.

        j-major cell order (j outer, i inner); with ``dst_filter`` a
        cell is kept only when some frontier-active edge of its chunk
        lands in its block — the others contribute only the identity.

        ``max_live_fraction`` implements 'auto' cheaply: when the dense
        grid's live fraction is at/above it, return (None, None) BEFORE
        any per-cell work — a dense frontier gains nothing from the 1-D
        launch.  Plans whose scalar-prefetch tables exceed
        ``smem_budget_bytes`` warn once per planner."""
        act, live_chunk = self._active(gchg)
        dense_live = int(self.span[live_chunk].sum())
        if max_live_fraction is not None \
                and dense_live / max(self.total_cells, 1) \
                >= max_live_fraction:
            return None, None
        jj, ii = self._cells(live_chunk)
        if dst_filter:
            keep = (act[jj] & (self.blk_of[jj] == ii[:, None])).any(axis=1)
            jj, ii = jj[keep], ii[keep]
        nlive = int(ii.shape[0])
        l_pad = _wl_pad_len(nlive, pad_to)
        wl_i = np.zeros(l_pad, np.int32)
        wl_j = np.zeros(l_pad, np.int32)
        wl_i[:nlive] = ii
        wl_j[:nlive] = jj
        wl = Worklist(wl_i, wl_j, np.asarray([nlive], np.int32))
        info = WorklistInfo(
            cells=nlive, launched=l_pad, dense_live=dense_live,
            smem_table_bytes=smem_table_bytes(self.n_chunks, l_pad))
        if self.smem_budget_bytes is not None and not self._smem_warned \
                and info.smem_table_bytes > self.smem_budget_bytes:
            self._smem_warned = True
            warnings.warn(
                f"worklist scalar-prefetch tables ({info.launched} cells, "
                f"{self.n_chunks} chunks) weigh {info.smem_table_bytes} "
                f"bytes — over smem_budget_bytes={self.smem_budget_bytes}; "
                "prefer grid_mode='auto' (dense frontiers keep the dense "
                "grid)", stacklevel=2)
        return wl, info


def plan_worklist(edge_dst, edge_mask, edge_src, gchg, num_segments: int,
                  *, pad_to: int = WL_PAD, dst_filter: bool = True):
    """One-shot worklist plan (see ``WorklistPlanner`` for the reusable
    form drivers amortize across rounds).  ``gchg`` is the (V,) frontier
    (OR across lanes for laned launches)."""
    planner = WorklistPlanner(edge_dst, edge_mask, edge_src, num_segments)
    return planner.plan(gchg, pad_to=pad_to, dst_filter=dst_filter)


# --------------------------------------------------------------------------
# device-side worklist compaction (grid_mode='device_worklist')
# --------------------------------------------------------------------------
# The traced twin of WorklistPlanner.plan: the live-cell list is built
# from the same jnp chunk tables the dense kernels prefetch, compacted
# j-major by a cumsum-scatter, and fed to the worklist kernels as
# scalar-prefetch operands (ordinary pallas_call inputs, so traced values
# are fine — only host planning demands concreteness).  The launch length
# is static, so whole fixpoints run inside one `lax.while_loop` /
# `shard_map` trace with the tail masked by the kernels' `c < nlive`
# guard.  The device list applies no dst filter, so its exact host oracle
# is ``WorklistPlanner.plan(gchg, dst_filter=False)``: cells == the dense
# grid's live count.


def device_worklist_pad(num_edges: int, num_segments: int) -> int:
    """Static 1-D launch length of the device-compacted worklist: the
    pow2-padded ``n_chunks + n_sblk``.  Destination-sorted edges put each
    chunk's ids in one contiguous block range and the ranges of
    consecutive chunks overlap in at most one block, so the live cells of
    one sorted edge run never exceed that; other layouts fall back to the
    dense grid when they do.  Round-invariant, so a fixpoint traces
    once."""
    n_i = _round_up(num_segments, SBLK) // SBLK
    n_chunks = _round_up(num_edges, EBLK) // EBLK
    return _wl_pad_len(n_chunks + n_i)


def _compact_live_cells(chunk_lo, chunk_hi, chunk_act, n_i: int,
                        l_pad: int):
    """Cumsum-scatter frontier compaction: the (n_i, n_chunks) live-cell
    matrix (the dense grid's two-level skip), flattened j-major — the
    exact cell order ``WorklistPlanner.plan`` emits — into fixed-shape
    ``wl_i``/``wl_j`` plus the (1,) live count.  Dead cells, and live
    cells past ``l_pad``, scatter out of bounds and are dropped; the
    padded tail keeps index 0 (cell (0, 0)), never executed."""
    n_chunks = chunk_lo.shape[0]
    seg0 = jnp.arange(n_i, dtype=jnp.int32)[:, None] * SBLK
    intersects = (chunk_hi[None, :] >= seg0) & (chunk_lo[None, :]
                                                < seg0 + SBLK)
    live = (intersects & (chunk_act[None, :] > 0)).T.reshape(-1)
    k = jnp.arange(n_chunks * n_i, dtype=jnp.int32)
    pos = jnp.cumsum(live.astype(jnp.int32)) - 1
    idx = jnp.where(live, pos, l_pad)
    wl_i = jnp.zeros((l_pad,), jnp.int32).at[idx].set(k % n_i,
                                                      mode="drop")
    wl_j = jnp.zeros((l_pad,), jnp.int32).at[idx].set(k // n_i,
                                                      mode="drop")
    nlive = live.sum(dtype=jnp.int32).reshape(1)
    return wl_i, wl_j, nlive


@functools.partial(jax.jit, static_argnames=("num_segments", "l_pad"))
def _device_worklist_arrays(gchg, edge_src, edge_mask, edge_dst,
                            num_segments, l_pad):
    """Jitted device-worklist builder.  ``gchg`` may be (V,) or laned
    (V, Q) — laned frontiers are OR'd across lanes exactly as the host
    planner plans them."""
    e_pad = _round_up(edge_src.shape[0], EBLK)
    gchg = jnp.asarray(gchg)
    if gchg.ndim == 2:
        gchg = gchg.any(axis=-1)
    pad = (0, e_pad - edge_src.shape[0])
    active = jnp.pad(edge_mask & jnp.take(gchg, edge_src), pad)
    tables = _chunk_tables(jnp.pad(edge_dst.astype(jnp.int32), pad),
                           jnp.pad(edge_mask, pad), active)
    return _compact_live_cells(
        *tables, _round_up(num_segments, SBLK) // SBLK, l_pad)


def build_device_worklist(gchg, edge_src, edge_mask, edge_dst,
                          num_segments: int):
    """The ``grid_mode='device_worklist'`` plan as a :class:`Worklist`
    whose leaves are traced device arrays, ``device_worklist_pad`` long
    — works under jit/shard_map, where host planning
    (``grid_mode='worklist'``) cannot."""
    return Worklist(*_device_worklist_arrays(
        gchg, edge_src, edge_mask, edge_dst, num_segments=num_segments,
        l_pad=device_worklist_pad(edge_src.shape[0], num_segments)))


# --------------------------------------------------------------------------
# launches
# --------------------------------------------------------------------------

def _require_concrete(x, what: str):
    if isinstance(x, jax.core.Tracer):
        raise ValueError(
            f"grid_mode='worklist' plans the launch host-side, so {what} "
            "must be concrete — under jit, build the plan outside the "
            "trace (WorklistPlanner.plan) and pass it via worklist=")


def _launch_worklist(gchg, edge_src, edge_mask, edge_dst, num_segments):
    """Concrete-input convenience: plan the worklist at launch time (the
    differential tests drive this; round drivers pre-plan instead)."""
    _require_concrete(gchg, "the frontier")
    gchg_np = np.asarray(gchg)
    if gchg_np.ndim == 2:                        # laned: OR across lanes
        gchg_np = gchg_np.any(axis=-1)
    wl, _ = plan_worklist(np.asarray(edge_dst), np.asarray(edge_mask),
                          np.asarray(edge_src), gchg_np, num_segments)
    return wl


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "relax_kind", "kind", "interpret",
                     "with_count", "with_debug", "grid_mode"))
def _fused(gval, gchg, edge_src, edge_w, edge_mask, edge_dst, worklist, *,
           num_segments, relax_kind, kind, interpret, with_count,
           with_debug, grid_mode):
    e_pad = _round_up(edge_src.shape[0], EBLK)
    ids_p, msg, valid, active = _edge_messages(
        gval, gchg, edge_src, edge_w, edge_mask, edge_dst, relax_kind,
        kind, e_pad)
    raw = _reduce_launch(ids_p, msg, valid, active, num_segments, kind,
                         False, interpret, with_debug, grid_mode, worklist)
    return _finish(
        raw, with_debug,
        lambda o: o.reshape(-1)[:num_segments].astype(gval.dtype),
        active.sum(), with_count)


def fused_relax_reduce_pallas(gval, gchg, edge_src, edge_w, edge_mask,
                              edge_dst, num_segments: int, relax_kind: str,
                              kind: str, *, interpret: bool,
                              with_count: bool = False,
                              with_debug: bool = False,
                              grid_mode: str = "dense", worklist=None,
                              smem_budget_bytes=None):
    """Fused gather/relax/mask/segment-reduce.

    gval: (V,) f32 vertex (replica-slot) values; gchg: (V,) bool changed
    flags (the frontier); edge_src/edge_dst: (E,) int32 into [0, V) /
    [0, num_segments); edge_w: (E,) f32; edge_mask: (E,) bool (False on
    padding). Returns the (num_segments,) inbox partial — empty segments
    hold the combine identity.  ``with_count=True`` appends the
    active-edge count (a reduction of the frontier mask the launch builds
    anyway); ``with_debug=True`` appends the kernel-side (1,) int32
    executed-grid-cell counter that ``fused_grid_cells`` mirrors on the
    host.  Edges should be sorted by ``edge_dst`` for the range skip to
    bite; correctness never depends on the sort.  ``interpret`` is
    required: ``kernels.ops`` makes the one backend-based choice.

    ``grid_mode='worklist'`` (or an explicit ``worklist=`` plan) swaps
    the dense early-exit grid for the 1-D live-cell worklist launch, and
    ``grid_mode='device_worklist'`` compacts that list on device (traced
    — works inside jit/shard_map loops).  Bit-identical to the dense grid
    for min semirings (sum differs only by the partial scatter's
    reassociation).  ``smem_budget_bytes`` arms the scalar-prefetch
    table guard.
    """
    _check_pair(relax_kind, kind)
    n_chunks = _round_up(edge_src.shape[0], EBLK) // EBLK
    if worklist is None and grid_mode == "worklist":
        worklist = _launch_worklist(gchg, edge_src, edge_mask, edge_dst,
                                    num_segments)
    _warn_smem(n_chunks, 0 if worklist is None else worklist.l_pad,
               smem_budget_bytes)
    return _fused(gval, gchg, edge_src, edge_w, edge_mask, edge_dst,
                  worklist, num_segments=num_segments,
                  relax_kind=relax_kind, kind=kind, interpret=interpret,
                  with_count=with_count, with_debug=with_debug,
                  grid_mode=grid_mode)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "relax_kind", "kind", "interpret",
                     "with_count", "with_debug", "grid_mode", "q_pad"))
def _fused_lanes(gval, gchg, lane_unitw, edge_src, edge_w, edge_mask,
                 edge_dst, worklist, *, num_segments, relax_kind, kind,
                 interpret, with_count, with_debug, grid_mode, q_pad):
    q = gval.shape[1]
    e_pad = _round_up(edge_src.shape[0], EBLK)
    ids_p, msg, valid, active, counts = _lane_messages(
        gval, gchg, lane_unitw, edge_src, edge_w, edge_mask, edge_dst,
        relax_kind, kind, e_pad, q_pad)
    raw = _reduce_launch(ids_p, msg, valid, active, num_segments, kind,
                         True, interpret, with_debug, grid_mode, worklist)

    def unblock(out):                    # (n_i, q_pad, SBLK) -> (S, Q)
        flat = out.transpose(0, 2, 1).reshape(-1, q_pad)
        return flat[:num_segments, :q].astype(gval.dtype)

    return _finish(raw, with_debug, unblock, counts, with_count)


def fused_relax_reduce_lanes_pallas(gval, gchg, lane_unitw, edge_src, edge_w,
                                    edge_mask, edge_dst, num_segments: int,
                                    relax_kind: str, kind: str, *,
                                    interpret: bool,
                                    with_count: bool = False,
                                    lane_tile=None,
                                    with_debug: bool = False,
                                    grid_mode: str = "dense",
                                    worklist=None, smem_budget_bytes=None):
    """Lane-batched fused gather/relax/mask/segment-reduce.

    The single-query kernel grown a query-lane axis ``Q``:
    ``gval``/``gchg`` are (V, Q) — per-lane values and per-lane frontiers
    over one shared edge structure — and the result is the (num_segments,
    Q) per-lane inbox partial (plus, with ``with_count=True``, the (Q,)
    per-lane active-edge counts).  ``lane_unitw`` (Q,) only matters for
    ``relax_kind='add_w'``: lanes with a nonzero flag relax with the
    constant weight 1.0 (BFS levels) instead of the edge weight (SSSP), so
    one launch serves a mixed BFS/SSSP batch.  A converged lane has an
    all-False ``gchg`` column, so it contributes nothing while live lanes
    keep the round busy — and the chunk-skip bitmap is the OR across
    lanes, so a grid cell is skipped only when its edge chunk is
    frontier-dead in *every* lane.

    The lane axis is padded to a multiple of ``LANE_PAD`` (or of
    ``lane_tile``): tail lanes carry the identity, so they are masked out
    of every reduction and sliced off the output — a padded batch is
    bit-identical to the unpadded math.  Grid modes as in
    ``fused_relax_reduce_pallas``.
    """
    assert relax_kind in ("add_w", "mul_w"), relax_kind
    _check_pair(relax_kind, kind)
    q_pad = _lane_pad(gval.shape[1], lane_tile)
    n_chunks = _round_up(edge_src.shape[0], EBLK) // EBLK
    if worklist is None and grid_mode == "worklist":
        worklist = _launch_worklist(gchg, edge_src, edge_mask, edge_dst,
                                    num_segments)
    _warn_smem(n_chunks, 0 if worklist is None else worklist.l_pad,
               smem_budget_bytes)
    return _fused_lanes(gval, gchg, lane_unitw, edge_src, edge_w, edge_mask,
                        edge_dst, worklist, num_segments=num_segments,
                        relax_kind=relax_kind, kind=kind,
                        interpret=interpret, with_count=with_count,
                        with_debug=with_debug, grid_mode=grid_mode,
                        q_pad=q_pad)


# --------------------------------------------------------------------------
# host-side launch mirror (grid-cell accounting)
# --------------------------------------------------------------------------

def fused_grid_cells(edge_dst, edge_mask, edge_src, gchg,
                     num_segments: int, grid_mode: str = "dense",
                     pad_to: int = WL_PAD, dst_filter: bool = True) -> dict:
    """Host-side mirror of both launch shapes for the dense exchange.

    ``fused_live``/``total_fused`` mirror THIS kernel's single flattened
    launch (edge_mask-aware per-chunk ranges + frontier bitmap);
    ``range_live``/``total_unfused`` mirror the unfused composition's S
    vmapped per-shard ``segment_combine_pallas`` launches, whose validity
    rule is positional (every in-shard slot counts, so engine padding
    edges carrying id 0 widen chunk ranges) and which has no frontier
    skip.  Edge arrays are (S, E_max) host arrays — or 1-D for a single
    flat launch; ``gchg`` is the (V,) frontier (OR across lanes when
    mirroring a laned launch).  The cell counts must match the kernels'
    ``with_debug`` counter exactly.
    """
    edge_dst = np.atleast_2d(np.asarray(edge_dst))
    edge_mask = np.atleast_2d(np.asarray(edge_mask))
    edge_src = np.atleast_2d(np.asarray(edge_src))
    gchg = np.asarray(gchg).reshape(-1)
    S, E_max = edge_dst.shape
    s_pad = _round_up(num_segments, SBLK)
    seg0 = np.arange(s_pad // SBLK)[:, None] * SBLK        # (n_i, 1)

    # fused: one launch over the flattened edge stack
    e = S * E_max
    e_pad = _round_up(e, EBLK)
    ids = np.zeros(e_pad, np.int64)
    ids[:e] = edge_dst.reshape(-1)
    msk = np.zeros(e_pad, bool)
    msk[:e] = edge_mask.reshape(-1)
    act = np.zeros(e_pad, bool)
    act[:e] = edge_mask.reshape(-1) & gchg[edge_src.reshape(-1)]
    idc, mkc, acc = (x.reshape(e_pad // EBLK, EBLK) for x in (ids, msk, act))
    lo = np.where(mkc, idc, np.iinfo(np.int64).max).min(axis=1)
    hi = np.where(mkc, idc, -1).max(axis=1)
    intersects = (hi[None, :] >= seg0) & (lo[None, :] < seg0 + SBLK)
    fused_live = int((intersects & acc.any(axis=1)[None, :]).sum())

    # unfused: S per-shard launches, positional validity, range skip only
    ep = _round_up(E_max, EBLK)
    ids_s = np.zeros((S, ep), np.int64)
    ids_s[:, :E_max] = edge_dst
    valid = np.zeros(ep, bool)
    valid[:E_max] = True
    idc2 = ids_s.reshape(S, ep // EBLK, EBLK)
    v2 = valid.reshape(ep // EBLK, EBLK)[None, :, :]
    lo2 = np.where(v2, idc2, np.iinfo(np.int64).max).min(axis=-1)
    hi2 = np.where(v2, idc2, -1).max(axis=-1)                # (S, n_j)
    inter2 = (hi2[:, None, :] >= seg0[None, :, :]) \
        & (lo2[:, None, :] < seg0[None, :, :] + SBLK)        # (S, n_i, n_j)
    out = {
        "total_fused": int(intersects.size),
        "total_unfused": int(inter2.size),
        "range_live": int(inter2.sum()),
        "fused_live": fused_live,
    }
    if grid_mode == "worklist":
        # worklist-launch mirror: the planner is the host-side oracle —
        # cells launched after the dst-filter empty-cell drop, matched
        # EXACTLY by the worklist kernels' with_debug counter
        _, info = plan_worklist(edge_dst, edge_mask, edge_src, gchg,
                                num_segments, pad_to=pad_to,
                                dst_filter=dst_filter)
        out["wl_cells"] = info.cells
        out["wl_launched"] = info.launched
        out["smem_table_bytes"] = info.smem_table_bytes
    elif grid_mode == "device_worklist":
        # device-compaction mirror: no dst filter — cells are exactly the
        # dense grid's live count (the dense fallback runs the same cells)
        out["wl_cells"] = fused_live
        out["wl_launched"] = device_worklist_pad(e, num_segments)
        out["smem_table_bytes"] = smem_table_bytes(e_pad // EBLK,
                                                   out["wl_launched"])
    return out
