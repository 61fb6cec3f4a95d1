"""Diffusive fixpoint engine (paper §4–§5), TPU-native.

The paper's asynchronous message-driven execution is re-expressed as bulk
edge-parallel relaxation rounds whose fixpoint equals the asynchronous
fixpoint (monotone semirings ⇒ order-free). One round is the diffuse-queue
drain: diffusions generated in round k are evaluated in round k+1 against
the newest vertex state, so stale diffusions are *subsumed* exactly as the
paper's lazy-diffuse pruning does.

The per-round math — relax, dense or §Perf compact targeted exchange,
rhizome collapse — lives in the unified lane-generic exchange layer
(``repro.exchange``); this module is the *driver*: it owns the fixpoint
loops, termination collectives, and Fig-6 stats bookkeeping for the
single-query (unlaned) table layout.  ``repro.query.lanes`` drives the
same exchange layer with a trailing query-lane axis.

Two execution paths share the same per-round math:

* ``run_stacked``  — arrays stacked ``(S, …)`` on one device; collectives
  are reshapes/transposes.  Used for correctness tests at any shard count.
* ``run_sharded``  — ``shard_map`` over a mesh with real collectives:
  - value/changed broadcast  → ``all_gather``      (the diffusion fan-out)
  - inbox exchange           → ``all_to_all``      (messages to replicas)
  - rhizome collapse         → ``all_gather`` + sibling combine
    (the AND-gate LCO trigger, lowered to a counted reduction)
  - termination detection    → ``psum`` of the any-changed flag
    (the paper assumes a hardware idle signal; the collective is ours).

With ``EngineConfig.use_pallas`` the per-round relax phase dispatches
through the fused ``kernels.fused_relax_reduce`` Pallas kernel: the
gather/relax/mask fusion feeds a blocked segment reduction whose grid
cells over frontier-dead edge chunks are skipped entirely (the TPU form
of the paper's diffusion pruning).  Without the flag the same math runs as
separate jnp ops — the oracle path.

Per-round counters reproduce the paper's Fig-6 statistics: messages
(actions delivered), actions whose predicate fired (work performed), and
diffusions pruned.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import typing
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import exchange, obs
from repro.core.actions import Semiring
from repro.core.partition import Partition


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    collapse: str = "eager"      # 'eager' | 'deferred' (min-semirings only)
    exchange: str = "dense"      # 'dense' | 'compact' (targeted messages)
    max_iters: int = 4096
    use_pallas: bool = False     # route the relax phase through Pallas
    # 'fused'  — gather+relax+mask fusion feeding the blocked reduce
    #            kernel with frontier chunk skip (the hot path; default)
    # 'reduce' — jnp gather/relax/mask + the standalone segment-reduce
    #            kernel (the pre-fusion composition, kept for comparison)
    pallas_mode: str = "fused"
    # False skips the Fig-6 message counter (an O(E) boolean reduction per
    # round on the fused path); RunStats then reports zero messages/pruned
    track_stats: bool = True
    # Fused-kernel grid shape (ISSUE 5):
    # 'dense'    — the classic (num_sblk, num_chunks) grid with per-cell
    #              early exit (launch cost ∝ total work)
    # 'worklist' — host-planned 1-D launch over the live (i, j) cells
    #              only (launch cost ∝ frontier); requires a host-driven
    #              round loop, so it applies to the stacked runners and
    #              the delta rounds — traced collective loops
    #              (run_sharded's while_loop, the laned sharded fixpoint)
    #              warn once and route to 'device_worklist', the traced
    #              form of the same sparse launch
    # 'auto'     — per round: worklist when the live fraction of the
    #              dense grid drops below WORKLIST_AUTO_THRESHOLD
    # 'device_worklist' — the live-cell list is compacted ON DEVICE
    #              (cumsum-scatter over the frontier chunk bitmap) and
    #              launched over a static pow2-padded list with masked
    #              tail cells.  Fully traced — composes with
    #              jit/shard_map, so whole fixpoints run through
    #              lax.while_loop with zero host syncs (ISSUE 8)
    grid_mode: str = "dense"
    # Rounds per dispatch window for device_worklist loops that still
    # need periodic host visibility (an installed flight recorder, the
    # QueryServer default tick).  One download of the frontier
    # trajectory per window instead of per round.
    device_window: int = 8
    # SMEM byte budget for the fused kernel's scalar-prefetch tables
    # (chunk ranges, worklist cells).  None disables the guard; set to
    # the real-TPU SMEM size to make launches warn before a ~100k-chunk
    # table would overflow.
    smem_budget_bytes: int | None = None
    # Checkpoint cadence for the resilient driver (core.resilient): a
    # crc-verified snapshot of value/frontier state + accounting every K
    # rounds.  None disables (and keeps every shipped loop here exactly
    # as before — run_stacked never checkpoints; only the resilient
    # driver reads this knob, so the obs-off path stays trace-identical).
    checkpoint_every: int | None = None

    def __post_init__(self):
        if self.collapse not in ("eager", "deferred"):
            raise ValueError(f"collapse={self.collapse!r}")
        if self.exchange not in ("dense", "compact"):
            raise ValueError(f"exchange={self.exchange!r}")
        if self.pallas_mode not in ("fused", "reduce"):
            raise ValueError(f"pallas_mode={self.pallas_mode!r}")
        if self.grid_mode not in ("dense", "worklist", "auto",
                                  "device_worklist"):
            raise ValueError(f"grid_mode={self.grid_mode!r}")
        if self.device_window < 1:
            raise ValueError(f"device_window={self.device_window!r}")
        if self.checkpoint_every is not None and self.checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every={self.checkpoint_every!r}")
        if self.smem_budget_bytes is not None \
                and self.smem_budget_bytes <= 0:
            raise ValueError(
                f"smem_budget_bytes={self.smem_budget_bytes!r}")

    @property
    def wants_worklist(self) -> bool:
        """Whether runners should plan HOST-side sparse worklist launches
        (only meaningful on the fused Pallas path — the jnp oracle and
        the pre-fusion composition have no grid to sparsify)."""
        return (self.grid_mode in ("worklist", "auto") and self.use_pallas
                and self.pallas_mode == "fused")

    @property
    def wants_device_worklist(self) -> bool:
        """Whether the relax phase compacts its worklist on device —
        the traced launch mode that keeps whole fixpoints in one
        dispatch.  ``relax`` reads this straight off ``grid_mode``; the
        runners use it to pick the traced loop over the host loop."""
        return (self.grid_mode == "device_worklist" and self.use_pallas
                and self.pallas_mode == "fused")


class DeviceArrays(typing.NamedTuple):
    """Static per-shard tensors; leading dim S (stacked) or sharded.

    The ``edge_dst_compact``/``inbox_slot_map``/``rz_*`` fields implement
    the §Perf *compact targeted exchange*: contributions travel as
    (target, slot) messages instead of a dense global inbox — the TPU form
    of the paper's message-driven semantics."""

    edge_src_root_flat: jax.Array  # (S, E_max) int32
    edge_dst_flat: jax.Array       # (S, E_max) int32 (sorted per shard)
    edge_w: jax.Array              # (S, E_max) f32
    edge_mask: jax.Array           # (S, E_max) bool
    sibling_flat: jax.Array        # (S, R_max, K) int32
    sibling_mask: jax.Array        # (S, R_max, K) bool
    slot_valid: jax.Array          # (S, R_max) bool
    edge_dst_compact: jax.Array    # (S, E_max) int32 -> [0, S*P_t)
    inbox_slot_map: jax.Array      # (S, S, P_t) int32, R_max = pad
    rz_local: jax.Array            # (S, R_rz_max) int32, R_max = pad
    rz_sibling_idx: jax.Array      # (S, R_rz_max, K) int32
    rz_sibling_mask: jax.Array     # (S, R_rz_max, K) bool

    @classmethod
    def specs(cls, spec) -> "DeviceArrays":
        """Per-field shard_map spec tree (every field shares ``spec``) —
        the in_specs entry for every sharded runner over these tables."""
        return cls(*([spec] * len(cls._fields)))

    @classmethod
    def from_partition(cls, part: Partition) -> "DeviceArrays":
        """Upload the partition's tables (an ``engine.upload`` span)."""
        with obs.span("engine.upload") as sp:
            arrays = cls(
                edge_src_root_flat=jnp.asarray(part.edge_src_root_flat,
                                               jnp.int32),
                edge_dst_flat=jnp.asarray(part.edge_dst_flat, jnp.int32),
                edge_w=jnp.asarray(part.edge_w, jnp.float32),
                edge_mask=jnp.asarray(part.edge_mask),
                sibling_flat=jnp.asarray(part.sibling_flat, jnp.int32),
                sibling_mask=jnp.asarray(part.sibling_mask),
                slot_valid=jnp.asarray(part.slot_vertex >= 0),
                edge_dst_compact=jnp.asarray(part.edge_dst_compact,
                                             jnp.int32),
                inbox_slot_map=jnp.asarray(part.inbox_slot_map, jnp.int32),
                rz_local=jnp.asarray(part.rz_local, jnp.int32),
                rz_sibling_idx=jnp.asarray(part.rz_sibling_idx, jnp.int32),
                rz_sibling_mask=jnp.asarray(part.rz_sibling_mask),
            )
            sp.note(bytes=sum(x.nbytes for x in arrays))
        return arrays


class RunStats(typing.NamedTuple):
    iterations: jax.Array        # rounds executed
    messages: jax.Array          # actions delivered (edge messages)
    work_actions: jax.Array      # predicate-true slot updates
    pruned_actions: jax.Array    # delivered but predicate-false
    diffusions: jax.Array        # slots that diffused (entered the frontier)


# --------------------------------------------------------------------------
# per-round math: unified exchange-layer compositions (kept under their
# historic names — benchmarks and kernel-parity tests drive the rounds
# directly to measure exactly what the runners ship)
# --------------------------------------------------------------------------

def _fixpoint_round_stacked(sem, arrays, cfg, S, R_max, val, chg,
                            worklist=None):
    return exchange.fixpoint_round_stacked(
        sem, arrays, cfg, S, R_max, val, chg, worklist=worklist)


def _pagerank_round_stacked(sem, arrays, cfg, S, R_max, base, damping, val,
                            chg, worklist=None):
    return exchange.pagerank_round_stacked(
        sem, arrays, cfg, S, R_max, base, damping, val, chg,
        worklist=worklist)


# --------------------------------------------------------------------------
# worklist launch planning (grid_mode='worklist'|'auto' host-driven rounds)
# --------------------------------------------------------------------------

# 'auto' plans a worklist launch only when the dense grid's live fraction
# drops below this — a dense frontier gains nothing from the 1-D launch
# but pays the planning pass
WORKLIST_AUTO_THRESHOLD = 0.25


def launch_planner(part: Partition, cfg: EngineConfig):
    """Host-side ``WorklistPlanner`` for the stacked fused launch under
    ``cfg`` — the planner must mirror the exact launch ``relax`` builds:
    dense exchange flattens ``edge_dst_flat`` over ``S*R_max`` segments;
    compact exchange offsets ``edge_dst_compact`` into per-source-shard
    id windows over ``S*S*P_t``."""
    from repro.kernels.fused_relax_reduce import WorklistPlanner
    S, R_max = part.S, part.R_max
    with obs.span("engine.plan"):
        if cfg.exchange == "compact":
            P_t = part.P_t
            num_segments = S * S * P_t
            # offset in int32 unless the id windows overflow it
            ids = np.asarray(part.edge_dst_compact).astype(
                np.int32 if num_segments <= np.iinfo(np.int32).max
                else np.int64)
            ids += (np.arange(S, dtype=ids.dtype) * (S * P_t))[:, None]
        else:
            ids = np.asarray(part.edge_dst_flat)
            num_segments = S * R_max
        return WorklistPlanner(
            ids, np.asarray(part.edge_mask),
            np.asarray(part.edge_src_root_flat), num_segments,
            smem_budget_bytes=cfg.smem_budget_bytes)


def plan_round_worklist(planner, cfg: EngineConfig, gchg,
                        with_info: bool = False):
    """One round's launch decision for a host-driven loop: a ``Worklist``
    under 'worklist' (and under 'auto' when the frontier is sparse
    enough), else None — the dense early-exit grid.  The auto threshold
    is applied inside ``plan`` so a dense round bails out before any
    per-cell planning work.  ``with_info=True`` also returns the
    planner's ``WorklistInfo`` accounting (None for dense rounds) — the
    flight recorder's per-round mirror, captured for free from the plan
    the launch actually uses."""
    thresh = (WORKLIST_AUTO_THRESHOLD if cfg.grid_mode == "auto"
              else None)
    with obs.span("engine.plan") as sp:
        wl, info = planner.plan(gchg, max_live_fraction=thresh)
        if info is not None:
            sp.note(cells=info.cells, launched=info.launched)
    return (wl, info) if with_info else wl


def _obs_record_round(rec, run, part, cfg, planner, rnd, gchg, frontier,
                      mc, work, wl, info, wall_s):
    """Build + store one flight-recorder ``RoundRecord``: the grid-cell
    columns come from the planner mirror of the launch this round
    actually made (WorklistInfo for worklist launches, the dense-grid
    mirror otherwise), plus the per-shard message-volume mirror feeding
    the skew gauge.  Only ever called with a recorder installed — the
    obs-off hot path never reaches here."""
    grid = "dense" if wl is None else "worklist"
    path = cfg.pallas_mode if cfg.use_pallas else "jnp"
    if planner is not None and cfg.use_pallas \
            and cfg.pallas_mode == "fused":
        if wl is not None:
            cells, launched = info.cells, info.launched
        else:
            d = planner.dense_mirror(gchg)
            cells, launched = d["cells"], d["launched"]
    else:
        cells = launched = 0
    shard = exchange.shard_message_mirror(
        part.edge_mask, part.edge_src_root_flat, gchg)
    rec.add_round(
        obs.RoundRecord(
            run=run, round=rnd, frontier=frontier, messages=mc, work=work,
            pruned=mc - min(work, mc), grid=grid, path=path, cells=cells,
            launched=launched, wall_s=wall_s,
            shard_messages=[int(x) for x in shard]),
        frontier_bitmap=gchg.copy() if rec.keep_frontiers else None)


# --------------------------------------------------------------------------
# fixpoint apps (BFS / SSSP)
# --------------------------------------------------------------------------

def run_stacked(sem: Semiring, part: Partition, init_val: np.ndarray,
                cfg: EngineConfig = EngineConfig(), init_changed=None):
    """Single-device stacked execution. ``init_val``: (S, R_max) float32.
    ``init_changed`` (optional bool (S, R_max)) seeds the first frontier —
    used by incremental recompute to re-diffuse only mutation sites.

    Under ``cfg.grid_mode='worklist'|'auto'`` (fused Pallas only) the
    fixpoint runs as a host-driven round loop: each round's frontier
    plans a sparse worklist launch (``launch_planner``), so launch cost
    tracks the live frontier instead of the dense grid.  Values and
    stats are identical to the traced loop (min semirings are
    bit-identical)."""
    if sem.segment != "min":
        raise ValueError(
            "run_stacked drives monotone min-semiring fixpoints; the "
            "collapse of a combined candidate is only sound there — use "
            "run_pagerank_stacked for counted sum-semiring rounds")
    arrays = DeviceArrays.from_partition(part)
    S, R_max = part.S, part.R_max
    # an installed flight recorder also routes through the host-driven
    # loop (bit-identical values/stats for min semirings — the loop the
    # worklist grid already runs) so each round can be recorded without
    # adding syncs to the traced while_loop; with no recorder the
    # dispatch below is exactly the pre-obs one.  device_worklist keeps
    # the traced loop (its compaction is traced) — a recorder there
    # switches to the K-round windowed device loop, which downloads the
    # frontier trajectory once per window instead of once per round
    if cfg.wants_device_worklist:
        if obs.get_recorder() is not None:
            return _run_stacked_deviceloop(sem, part, arrays, cfg,
                                           init_val, init_changed)
    elif cfg.wants_worklist or obs.get_recorder() is not None:
        return _run_stacked_hostloop(sem, part, arrays, cfg, init_val,
                                     init_changed)

    def body(carry):
        val, chg, it, stats = carry
        new_val, new_chg, msg_count = exchange.fixpoint_round_stacked(
            sem, arrays, cfg, S, R_max, val, chg
        )
        work = new_chg.sum()
        stats = RunStats(
            iterations=stats.iterations + 1,
            messages=stats.messages + msg_count,
            work_actions=stats.work_actions + work,
            pruned_actions=stats.pruned_actions
            + msg_count - jnp.minimum(work, msg_count),
            diffusions=stats.diffusions + work,
        )
        return new_val, new_chg, it + 1, stats

    def cond(carry):
        _, chg, it, _ = carry
        return jnp.any(chg) & (it < cfg.max_iters)

    if init_changed is not None:
        init_chg = jnp.asarray(init_changed) & arrays.slot_valid
    else:
        init_chg = sem.improved(
            jnp.asarray(init_val),
            jnp.full_like(jnp.asarray(init_val), sem.identity)
        ) & arrays.slot_valid
    zero = jnp.zeros((), jnp.int64) if jax.config.jax_enable_x64 else jnp.zeros((), jnp.int32)
    stats0 = RunStats(zero, zero, zero, zero, zero)
    # the whole fixpoint is ONE traced dispatch; the caller's read of its
    # results (``vertex_values``) is its single host sync
    with _dispatch(sem.name, 1):
        val, chg, it, stats = lax.while_loop(
            cond, body, (jnp.asarray(init_val), init_chg, zero, stats0))
    if cfg.collapse == "deferred":
        val = exchange.collapse(sem, val.reshape(-1), arrays.sibling_flat,
                                arrays.sibling_mask)
    return val, stats


def _count_dispatches(run: str, dispatches: int, host_syncs: int):
    """Registry accounting for the BENCH dispatch/host-sync columns:
    how many jitted dispatches a fixpoint issued and how many
    device→host sync points (frontier/result downloads) it paid.  Host
    loops pay one of each per round; device_worklist loops one per
    K-round window — or one per whole fixpoint with no recorder."""
    m = obs.registry()
    m.counter(
        "engine_dispatches_total",
        "jitted dispatches issued by engine fixpoint loops"
    ).labels(run=run).inc(dispatches)
    m.counter(
        "engine_host_syncs_total",
        "device->host sync points paid by engine fixpoint loops"
    ).labels(run=run).inc(host_syncs)


def _dispatch(run: str, rnd: int) -> obs.ProgramSpan:
    """``engine.dispatch`` span around the call that enqueues one device
    program (tracing and compiling it first where no cached program
    fits), counted in ``engine_dispatches_total``; ``rnd`` is the first
    round the program runs."""
    _count_dispatches(run, 1, 0)
    return obs.span("engine.dispatch", track=f"engine/{run}", run=run,
                    round=rnd)


def _sync(run: str, rnd: int) -> obs.ProgramSpan:
    """``engine.sync`` span around one device->host read, counted in
    ``engine_host_syncs_total`` where it happens; ``rnd`` is the round
    whose results it reads (0: the entering frontier)."""
    _count_dispatches(run, 0, 1)
    return obs.span("engine.sync", track=f"engine/{run}", run=run,
                    round=rnd)


_APP_CALLS = itertools.count(1)


def app_span(name: str):
    """Decorator of an app entry point: one ``app.<name>`` span per call,
    whose ``call`` argument counts app calls process-wide; the engine's
    spans of the call nest under it."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with obs.span(f"app.{name}", call=next(_APP_CALLS)):
                return fn(*args, **kwargs)
        return call
    return wrap


def _host_stats(it, msgs, work, pruned):
    dtype = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    mk = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    return RunStats(iterations=mk(it), messages=mk(msgs),
                    work_actions=mk(work), pruned_actions=mk(pruned),
                    diffusions=mk(work))


def _run_stacked_hostloop(sem, part, arrays, cfg, init_val, init_changed):
    """Worklist-mode fixpoint: the traced ``lax.while_loop`` becomes a
    Python loop so each round's frontier can plan its launch host-side.
    One jitted round fn serves every round — jit retraces only when the
    worklist's power-of-two length bucket changes (O(log cells) traces)
    or a dense round passes ``worklist=None``.

    Also the flight-recorder path for ``grid_mode='dense'``: with a
    recorder installed each round appends a ``RoundRecord`` (frontier,
    messages, planner-mirror cells, path decision, wall time) —
    recorder-only host work, after the round's existing frontier
    download."""
    S, R_max = part.S, part.R_max
    rec = obs.get_recorder()
    planner = (launch_planner(part, cfg)
               if cfg.wants_worklist
               or (rec is not None and cfg.use_pallas
                   and cfg.pallas_mode == "fused")
               else None)

    @jax.jit
    def round_fn(val, chg, worklist):
        return exchange.fixpoint_round_stacked(
            sem, arrays, cfg, S, R_max, val, chg, worklist=worklist)

    val = jnp.asarray(init_val)
    if init_changed is not None:
        chg = jnp.asarray(init_changed) & arrays.slot_valid
    else:
        chg = sem.improved(val, jnp.full_like(val, sem.identity)) \
            & arrays.slot_valid
    with _sync(sem.name, 0):
        chg_h = np.asarray(chg)    # ONE frontier download per round:
    it = msgs = work_total = pruned = 0   # reused for plan + accounting
    while it < cfg.max_iters:
        if not chg_h.any():
            break
        gchg = chg_h.reshape(-1)
        wl = info = None
        if cfg.wants_worklist:
            wl, info = plan_round_worklist(planner, cfg, gchg,
                                           with_info=True)
        frontier = int(gchg.sum()) if rec is not None else 0
        t0 = rec.tracer.now() if rec is not None else 0.0
        with obs.span("engine.round", track=f"engine/{sem.name}",
                      round=it + 1) as rnd:
            with _dispatch(sem.name, it + 1):
                val, chg, mc = round_fn(val, chg, wl)
            with _sync(sem.name, it + 1):
                chg_h = np.asarray(chg)
                mc = int(mc)
            if rec is not None:
                rnd.note(frontier=frontier, messages=mc)
        work = int(chg_h.sum())
        it += 1
        msgs += mc
        work_total += work
        pruned += mc - min(work, mc)
        if rec is not None:
            wall = rec.tracer.now() - t0
            _obs_record_round(rec, sem.name, part, cfg, planner, it, gchg,
                              frontier, mc, work, wl, info, wall)
    stats = _host_stats(it, msgs, work_total, pruned)
    if cfg.collapse == "deferred":
        val = exchange.collapse(sem, val.reshape(-1), arrays.sibling_flat,
                                arrays.sibling_mask)
    return val, stats


def _record_device_window(rec, run, part, planner, l_pad, window, it_end,
                          counts_h, ent, wall):
    """Post-hoc accounting for one K-round device window, recomputed
    from the frontier trajectory the dispatch returned: ``ent[r]`` is
    round r's ENTERING frontier bitmap (flattened), ``ent[k]`` the
    window's exit frontier, ``counts_h[r]`` the round's message count.
    Rounds whose entering frontier is empty are no-ops under every
    semiring (absorbing identity) — they ran on device but count as
    zero rounds, matching the host loop's early exit.  Appends ONE
    per-window ``RoundRecord`` (``window`` field set; per-round cells /
    shard mirrors summed over the window's live rounds, so window
    sums equal the per-round host-driven totals) and returns
    (live_rounds, messages, work, pruned)."""
    k = counts_h.shape[0]
    live_rounds = msgs = work = pruned = 0
    cells = 0
    shard_sum = None
    for r in range(k):
        if not ent[r].any():
            break
        live_rounds += 1
        mc = int(counts_h[r])
        wk = int(ent[r + 1].sum())
        msgs += mc
        work += wk
        pruned += mc - min(wk, mc)
        d = planner.dense_mirror(ent[r])
        cells += d["cells"]
        sh = np.asarray(exchange.shard_message_mirror(
            part.edge_mask, part.edge_src_root_flat, ent[r]))
        shard_sum = sh if shard_sum is None else shard_sum + sh
    if rec is not None:
        rec.add_round(
            obs.RoundRecord(
                run=run, round=it_end, frontier=int(ent[0].sum()),
                messages=msgs, work=work, pruned=pruned,
                grid="device_worklist", path="fused", cells=cells,
                launched=l_pad * live_rounds, wall_s=wall,
                shard_messages=([int(x) for x in shard_sum]
                                if shard_sum is not None else None),
                window=window),
            frontier_bitmap=ent[0].copy() if rec.keep_frontiers else None)
    return live_rounds, msgs, work, pruned


def _device_l_pad(planner) -> int:
    """Launch length of the stacked device_worklist launch the planner
    mirrors (one flat edge set)."""
    from repro.kernels.fused_relax_reduce import EBLK, device_worklist_pad
    return device_worklist_pad(planner.n_chunks * EBLK,
                               planner.num_segments)


def _run_stacked_deviceloop(sem, part, arrays, cfg, init_val, init_changed):
    """Recorder-visible device_worklist fixpoint: K-round windows
    (``cfg.device_window``), each ONE traced dispatch through
    ``exchange.fixpoint_window_stacked``.  The host sees the frontier
    trajectory once per window — the flight recorder's per-window
    ``RoundRecord`` mirrors are recomputed post-hoc from it, never from
    extra syncs inside the loop.  With no recorder installed
    ``run_stacked`` skips this loop entirely and runs the whole
    fixpoint as a single traced while_loop dispatch."""
    S, R_max = part.S, part.R_max
    rec = obs.get_recorder()
    planner = launch_planner(part, cfg)
    l_pad = _device_l_pad(planner)

    window_fns: dict = {}

    def window_fn(k):
        if k not in window_fns:
            window_fns[k] = jax.jit(
                lambda v, c, _k=k: exchange.fixpoint_window_stacked(
                    sem, arrays, cfg, S, R_max, _k, v, c))
        return window_fns[k]

    val = jnp.asarray(init_val)
    if init_changed is not None:
        chg = jnp.asarray(init_changed) & arrays.slot_valid
    else:
        chg = sem.improved(val, jnp.full_like(val, sem.identity)) \
            & arrays.slot_valid
    with _sync(sem.name, 0):
        chg_h = np.asarray(chg)
    it = msgs = work_total = pruned = 0
    window = 0
    while it < cfg.max_iters and chg_h.any():
        k = min(cfg.device_window, cfg.max_iters - it)
        window += 1
        t0 = rec.tracer.now() if rec is not None else 0.0
        with obs.span("engine.window", track=f"engine/{sem.name}",
                      window=window) as win:
            with _dispatch(sem.name, it + 1):
                val, chg, counts, frontiers = window_fn(k)(val, chg)
            with _sync(sem.name, it + 1):
                chg_h = np.asarray(chg)
                frontiers, counts = np.asarray(frontiers), \
                    np.asarray(counts)
            wall = rec.tracer.now() - t0 if rec is not None else 0.0
            ent = np.concatenate(
                [frontiers.reshape(k, -1).astype(bool),
                 chg_h.reshape(1, -1)], axis=0)
            live, w_msgs, w_work, w_pruned = _record_device_window(
                rec, sem.name, part, planner, l_pad, window,
                it + int((ent[:k].any(axis=1)).sum()), counts, ent, wall)
            if rec is not None:
                win.note(frontier=int(ent[0].sum()), messages=w_msgs,
                         rounds=live)
        it += live
        msgs += w_msgs
        work_total += w_work
        pruned += w_pruned
    stats = _host_stats(it, msgs, work_total, pruned)
    if cfg.collapse == "deferred":
        val = exchange.collapse(sem, val.reshape(-1), arrays.sibling_flat,
                                arrays.sibling_mask)
    return val, stats


# --------------------------------------------------------------------------
# PageRank-style counted-iteration apps
# --------------------------------------------------------------------------

def run_pagerank_stacked(part: Partition, damping: float, iters: int,
                         cfg: EngineConfig = EngineConfig()):
    from repro.core.actions import PAGERANK as sem

    arrays = DeviceArrays.from_partition(part)
    S, R_max = part.S, part.R_max
    base = (1.0 - damping) / part.n

    # initial score 1/n on every replica (consistent view)
    val0 = jnp.where(arrays.slot_valid, 1.0 / part.n, 0.0)
    chg = arrays.slot_valid  # PR predicate is #t — always diffuse
    # the frontier never changes, so a host-planned worklist is planned
    # once (from the host's copy of it) and reused by every traced round
    wl = (plan_round_worklist(launch_planner(part, cfg), cfg,
                              (np.asarray(part.slot_vertex) >= 0)
                              .reshape(-1))
          if cfg.wants_worklist else None)

    def body(_, val):
        new_val, _ = exchange.pagerank_round_stacked(
            sem, arrays, cfg, S, R_max, base, damping, val, chg,
            worklist=wl)
        return new_val

    # ONE dispatch for all ``iters`` rounds; the caller's read of the
    # scores (``vertex_values``) is its single host sync
    with _dispatch("pagerank", 1):
        val = lax.fori_loop(0, iters, body, val0)
    return val


def _tol_table(part: Partition, tol):
    """Per-slot residual tolerance: a scalar passes through; an (n,)
    per-vertex array maps every replica of vertex v to ``tol[v]``
    (invalid slots get +inf — they never diffuse)."""
    tol_arr = np.asarray(tol, np.float32)
    if tol_arr.ndim == 0:
        return jnp.asarray(float(tol_arr), jnp.float32)
    if tol_arr.shape != (part.n,):
        raise ValueError(
            f"per-vertex tol must be shape ({part.n},); got {tol_arr.shape}")
    sv = np.asarray(part.slot_vertex)
    table = np.where(sv >= 0, tol_arr[np.maximum(sv, 0)], np.inf)
    return jnp.asarray(table, jnp.float32)


def run_pagerank_delta(part: Partition, damping: float = 0.85,
                       tol=1e-6, cfg: EngineConfig = EngineConfig(),
                       max_rounds: int = 256,
                       init_rank=None, init_delta=None):
    """Stacked **delta-PageRank**: push-based residual propagation with
    per-vertex pruning (ISSUE 5 tentpole).

    Ranks accumulate the Neumann series ``Σ_k (d·Aᵀ)^k base`` — the same
    fixpoint the dense power iteration converges to — but each round
    diffuses only residual deltas above ``tol`` (scalar or (n,)
    per-vertex), so the frontier *shrinks* as residuals decay (by ~d per
    round) and the fused kernel's chunk-skip / worklist launch / tile
    filter all fire for the sum semiring.  Dropping sub-tolerance
    residuals bounds the rank error by O(tol / (1-d)) per vertex.

    Runs host-driven (the termination test and any worklist planning
    need the frontier on host).  Returns ((S, R_max) ranks, RunStats
    with the Fig-6 accounting: messages delivered, slots whose residual
    stayed live (work), deliveries pruned below tolerance).

    ``init_rank`` / ``init_delta`` warm-start the Neumann accumulation:
    streaming maintenance seeds the migrated old ranks plus a (possibly
    negative) residual correction on mutated vertices, so only the
    affected region re-diffuses (frontier tests use ``|delta|``)."""
    from repro.core.actions import PAGERANK as sem

    arrays = DeviceArrays.from_partition(part)
    S, R_max = part.S, part.R_max
    base = (1.0 - damping) / part.n
    tol_t = _tol_table(part, tol)
    if init_rank is None:
        rank0 = delta0 = jnp.where(arrays.slot_valid, base, 0.0)
    else:
        rank0 = jnp.asarray(init_rank, jnp.float32)
        delta0 = jnp.asarray(init_delta, jnp.float32)
    if cfg.wants_device_worklist:
        return _run_pagerank_delta_deviceloop(
            sem, part, arrays, cfg, damping, tol_t, rank0, delta0,
            max_rounds)
    rec = obs.get_recorder()
    planner = (launch_planner(part, cfg)
               if cfg.wants_worklist
               or (rec is not None and cfg.use_pallas
                   and cfg.pallas_mode == "fused")
               else None)

    @jax.jit
    def round_fn(rank, delta, worklist):
        return exchange.delta_pagerank_round_stacked(
            sem, arrays, cfg, S, R_max, damping, tol_t, rank, delta,
            worklist=worklist)

    rank, delta = rank0, delta0
    # each round returns next round's frontier — computed on device,
    # downloaded ONCE per round for planning + accounting alike
    with _sync("pagerank_delta", 0):
        chg_h = np.asarray((jnp.abs(delta) > tol_t) & arrays.slot_valid)
    it = msgs = work_total = pruned = 0
    while it < max_rounds:
        if not chg_h.any():
            break
        gchg = chg_h.reshape(-1)
        wl = info = None
        if cfg.wants_worklist:
            wl, info = plan_round_worklist(planner, cfg, gchg,
                                           with_info=True)
        frontier = int(gchg.sum()) if rec is not None else 0
        t0 = rec.tracer.now() if rec is not None else 0.0
        with obs.span("engine.round", track="engine/pagerank_delta",
                      round=it + 1) as rnd:
            with _dispatch("pagerank_delta", it + 1):
                rank, delta, chg, mc = round_fn(rank, delta, wl)
            with _sync("pagerank_delta", it + 1):
                chg_h = np.asarray(chg)
                mc = int(mc)
            if rec is not None:
                rnd.note(frontier=frontier, messages=mc)
        work = int(chg_h.sum())
        it += 1
        msgs += mc
        work_total += work
        pruned += mc - min(work, mc)
        if rec is not None:
            wall = rec.tracer.now() - t0
            _obs_record_round(rec, "pagerank_delta", part, cfg, planner,
                              it, gchg, frontier, mc, work, wl, info, wall)
    return rank, _host_stats(it, msgs, work_total, pruned)


def _run_pagerank_delta_deviceloop(sem, part, arrays, cfg, damping, tol_t,
                                   rank0, delta0, max_rounds):
    """delta-PageRank under ``grid_mode='device_worklist'``: the
    residual-tolerance frontier test runs ON DEVICE, so with no flight
    recorder the whole fixpoint is ONE traced ``lax.while_loop``
    dispatch; with a recorder it runs in K-round windows
    (``cfg.device_window``) whose per-round accounting is recomputed
    post-hoc from the returned frontier trajectory."""
    S, R_max = part.S, part.R_max
    rec = obs.get_recorder()
    rank, delta = rank0, delta0

    if rec is None:
        @jax.jit
        def fixpoint(rank, delta):
            zero = (jnp.zeros((), jnp.int64)
                    if jax.config.jax_enable_x64
                    else jnp.zeros((), jnp.int32))

            def body(carry):
                rank, delta, it, msgs, work, pruned = carry
                nr, nd, nchg, mc = exchange.delta_pagerank_round_stacked(
                    sem, arrays, cfg, S, R_max, damping, tol_t, rank,
                    delta)
                mc = mc.astype(zero.dtype)
                wk = nchg.sum(dtype=zero.dtype)
                return (nr, nd, it + 1, msgs + mc, work + wk,
                        pruned + mc - jnp.minimum(wk, mc))

            def cond(carry):
                _, delta, it, _, _, _ = carry
                live = jnp.any((jnp.abs(delta) > tol_t)
                               & arrays.slot_valid)
                return live & (it < max_rounds)

            return lax.while_loop(
                cond, body, (rank, delta, zero, zero, zero, zero))

        with _dispatch("pagerank_delta", 1):
            rank, delta, it, msgs, work, pruned = fixpoint(rank, delta)
        with _sync("pagerank_delta", 1):
            counts = [int(x) for x in (it, msgs, work, pruned)]
        return rank, _host_stats(*counts)

    planner = launch_planner(part, cfg)
    l_pad = _device_l_pad(planner)

    window_fns: dict = {}

    def window_fn(k):
        if k not in window_fns:
            window_fns[k] = jax.jit(
                lambda r, d, _k=k:
                exchange.delta_pagerank_window_stacked(
                    sem, arrays, cfg, S, R_max, _k, damping, tol_t, r, d))
        return window_fns[k]

    with _sync("pagerank_delta", 0):
        chg_h = np.asarray((jnp.abs(delta) > tol_t) & arrays.slot_valid)
    it = msgs = work_total = pruned = 0
    window = 0
    while it < max_rounds and chg_h.any():
        k = min(cfg.device_window, max_rounds - it)
        window += 1
        t0 = rec.tracer.now()
        with obs.span("engine.window", track="engine/pagerank_delta",
                      window=window) as win:
            with _dispatch("pagerank_delta", it + 1):
                rank, delta, chg, counts, frontiers = \
                    window_fn(k)(rank, delta)
            with _sync("pagerank_delta", it + 1):
                chg_h = np.asarray(chg)
                frontiers, counts = np.asarray(frontiers), \
                    np.asarray(counts)
            wall = rec.tracer.now() - t0
            ent = np.concatenate(
                [frontiers.reshape(k, -1).astype(bool),
                 chg_h.reshape(1, -1)], axis=0)
            live, w_msgs, w_work, w_pruned = _record_device_window(
                rec, "pagerank_delta", part, planner, l_pad, window,
                it + int((ent[:k].any(axis=1)).sum()), counts, ent, wall)
            win.note(frontier=int(ent[0].sum()), messages=w_msgs,
                     rounds=live)
        it += live
        msgs += w_msgs
        work_total += w_work
        pruned += w_pruned
    return rank, _host_stats(it, msgs, work_total, pruned)


def make_sharded_pagerank_delta_fn(S: int, R_max: int, damping: float,
                                   tol: float, mesh: Mesh,
                                   axis_names=("data", "model"),
                                   cfg: EngineConfig = EngineConfig()):
    """shard_map delta-PageRank round as a jit-able fn of (DeviceArrays,
    rank, delta) -> (rank, delta, psum'd count, psum'd live-slot count).
    The serving loop drives it un-looped (the frontier-empty termination
    lives on host); host-planned worklist modes route to the traced
    ``device_worklist`` launch inside shard_map (``_sharded_cfg``)."""
    from repro.core.actions import PAGERANK as sem

    cfg = _sharded_cfg(cfg, "make_sharded_pagerank_delta_fn")
    axis_names = exchange.axis_tuple(axis_names)
    spec = P(axis_names)

    in_specs = (DeviceArrays.specs(spec), spec, spec)

    def shard_fn(arrays_l: DeviceArrays, rank_l, delta_l):
        arrays_s = jax.tree.map(lambda x: x[0], arrays_l)
        new_rank, new_delta, new_chg, counts = \
            exchange.delta_pagerank_round_shard(
                sem, arrays_s, cfg, S, R_max, axis_names, damping, tol,
                rank_l[0], delta_l[0])
        counts = lax.psum(counts, axis_names)
        work = lax.psum(new_chg.sum(), axis_names)
        return new_rank[None], new_delta[None], counts[None], work[None]

    fn = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs,
        out_specs=(spec, spec, spec, spec), check_vma=False,
    )
    return jax.jit(fn), NamedSharding(mesh, spec)


def run_pagerank_delta_sharded(part: Partition, damping: float = 0.85,
                               tol: float = 1e-6, mesh: Mesh = None,
                               axis_names=("data", "model"),
                               cfg: EngineConfig = EngineConfig(),
                               max_rounds: int = 256,
                               init_rank=None, init_delta=None):
    """shard_map delta-PageRank execution (host-driven rounds over real
    collectives); layout as in ``run_sharded``.  Scalar ``tol`` only —
    a per-vertex table would need its own sharded layout."""
    if np.ndim(tol) != 0:
        raise ValueError("run_pagerank_delta_sharded takes a scalar tol")
    fn, sharding = make_sharded_pagerank_delta_fn(
        part.S, part.R_max, damping, float(tol), mesh, axis_names, cfg)
    arrays = DeviceArrays.from_partition(part)
    arrays_dev = jax.tree.map(lambda x: jax.device_put(x, sharding), arrays)
    slot_valid = np.asarray(part.slot_vertex) >= 0
    base = (1.0 - damping) / part.n
    if init_rank is None:
        init_rank = init_delta = jnp.where(jnp.asarray(slot_valid), base, 0.0)
    rank = jax.device_put(jnp.asarray(init_rank, jnp.float32), sharding)
    delta = jax.device_put(jnp.asarray(init_delta, jnp.float32), sharding)
    it = msgs = work_total = pruned = 0
    rec = obs.get_recorder()
    rec_path = cfg.pallas_mode if cfg.use_pallas else "jnp"
    run = "pagerank_delta_sharded"
    # the round's psum'd live-slot count IS the next round's frontier
    # size — only the initial frontier needs a host check
    with _sync(run, 0):
        delta_h = np.asarray(delta)
    live = bool(((np.abs(delta_h) > tol) & slot_valid).any())
    while live and it < max_rounds:
        if rec is not None:
            # recorder-only frontier download: the per-shard message
            # mirror needs the live-residual bitmap host-side
            with _sync(run, it):
                delta_h = np.asarray(delta)
            gchg = ((np.abs(delta_h) > tol) & slot_valid).reshape(-1)
            frontier = int(gchg.sum())
            t0 = rec.tracer.now()
        with obs.span("engine.round", track=f"engine/{run}",
                      round=it + 1) as rnd:
            with _dispatch(run, it + 1):
                rank, delta, counts, work = fn(arrays_dev, rank, delta)
            with _sync(run, it + 1):
                mc, w = int(counts[0]), int(work[0])
            if rec is not None:
                rnd.note(frontier=frontier, messages=mc)
        it += 1
        msgs += mc
        work_total += w
        pruned += mc - min(w, mc)
        live = w > 0
        if rec is not None:
            wall = rec.tracer.now() - t0
            shard = exchange.shard_message_mirror(
                part.edge_mask, part.edge_src_root_flat, gchg)
            rec.add_round(
                obs.RoundRecord(
                    run="pagerank_delta_sharded", round=it,
                    frontier=frontier, messages=mc, work=w,
                    pruned=mc - min(w, mc), grid="dense", path=rec_path,
                    cells=0, launched=0, wall_s=wall,
                    shard_messages=[int(x) for x in shard]),
                frontier_bitmap=gchg.copy() if rec.keep_frontiers
                else None)
    return rank, _host_stats(it, msgs, work_total, pruned)


# --------------------------------------------------------------------------
# sharded execution (shard_map over a real mesh)
# --------------------------------------------------------------------------

_SHARDED_GRID_WARNED: set = set()


def _sharded_cfg(cfg: EngineConfig, where: str) -> EngineConfig:
    """Traced collective loops cannot run host-planned worklists — they
    used to silently drop ``grid_mode='worklist'|'auto'`` to the dense
    fallback.  Now: warn once per call-site and route to the traced
    ``'device_worklist'`` launch — the same sparse-launch intent, with
    the compaction done on device inside the collective loop."""
    if cfg.grid_mode in ("worklist", "auto") and cfg.use_pallas \
            and cfg.pallas_mode == "fused":
        if where not in _SHARDED_GRID_WARNED:
            _SHARDED_GRID_WARNED.add(where)
            warnings.warn(
                f"{where}: grid_mode={cfg.grid_mode!r} needs a "
                "host-driven round loop, which a traced collective loop "
                "cannot run; routing to grid_mode='device_worklist' "
                "(on-device compaction — same sparse launch, no host "
                "sync)", stacklevel=3)
        return dataclasses.replace(cfg, grid_mode="device_worklist")
    return cfg


def make_sharded_fn(sem: Semiring, S: int, R_max: int,
                    mesh: Mesh, axis_names=("data", "model"),
                    cfg: EngineConfig = EngineConfig()):
    """Builds the shard_map diffusive fixpoint as a jit-able fn of
    (DeviceArrays, val) — usable with concrete arrays (run_sharded) or
    ShapeDtypeStructs (AOT dry-run lowering)."""
    if sem.segment != "min":
        raise ValueError(
            "make_sharded_fn drives monotone min-semiring fixpoints; use "
            "make_sharded_pagerank_fn for counted sum-semiring rounds")
    cfg = _sharded_cfg(cfg, "make_sharded_fn")
    axis_names = exchange.axis_tuple(axis_names)
    spec = P(axis_names)

    in_specs = (
        DeviceArrays.specs(spec),
        spec,
    )

    def shard_fn(arrays_l: DeviceArrays, val_l):
        # strip leading local shard dim of size 1
        arrays_s = jax.tree.map(lambda x: x[0], arrays_l)
        val = val_l[0]
        round_fn = exchange.make_shard_fixpoint_round(
            sem, arrays_s, cfg, S, R_max, axis_names)

        def body(carry):
            val, chg, it, stats = carry
            new_val, new_chg, msg_count = round_fn(val, chg)
            msgs = lax.psum(msg_count, axis_names)
            work = lax.psum(new_chg.sum(), axis_names)
            stats = RunStats(
                iterations=stats.iterations + 1,
                messages=stats.messages + msgs,
                work_actions=stats.work_actions + work,
                pruned_actions=stats.pruned_actions
                + msgs - jnp.minimum(work, msgs),
                diffusions=stats.diffusions + work,
            )
            return new_val, new_chg, it + 1, stats

        def cond(carry):
            _, chg, it, _ = carry
            anyc = lax.psum(chg.any().astype(jnp.int32), axis_names)
            return (anyc > 0) & (it < cfg.max_iters)

        init_chg = (
            sem.improved(val, jnp.full_like(val, sem.identity))
            & arrays_s.slot_valid
        )
        zero = jnp.zeros((), jnp.int32)
        stats0 = RunStats(zero, zero, zero, zero, zero)
        val, chg, it, stats = lax.while_loop(
            cond, body, (val, init_chg, zero, stats0)
        )
        if cfg.collapse == "deferred":
            val = exchange.collapse(
                sem, lax.all_gather(val, axis_names, tiled=True),
                arrays_s.sibling_flat, arrays_s.sibling_mask)
        return val[None], jax.tree.map(lambda x: x[None], stats)

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=in_specs,
        out_specs=(spec, RunStats(*([spec] * 5))),
        check_vma=False,
    )
    return jax.jit(fn), NamedSharding(mesh, spec)


def run_sharded(sem: Semiring, part: Partition, init_val: np.ndarray,
                mesh: Mesh, axis_names=("data", "model"),
                cfg: EngineConfig = EngineConfig()):
    """shard_map execution. Leading (shard) dim of every array is split over
    ``axis_names``; requires prod(mesh[axis_names]) == part.S."""
    fn, sharding = make_sharded_fn(
        sem, part.S, part.R_max, mesh, axis_names, cfg)
    arrays = DeviceArrays.from_partition(part)
    arrays_dev = jax.tree.map(lambda x: jax.device_put(x, sharding), arrays)
    val_dev = jax.device_put(jnp.asarray(init_val), sharding)
    # ONE dispatch; the caller's read of the results (``vertex_values``)
    # is its single host sync
    with _dispatch(f"{sem.name}_sharded", 1):
        val, stats = fn(arrays_dev, val_dev)
    stats = jax.tree.map(lambda x: x[0], stats)
    return val, stats


def make_sharded_pagerank_fn(S: int, R_max: int, n: int, damping: float,
                             iters: int, mesh: Mesh,
                             axis_names=("data", "model"),
                             cfg: EngineConfig = EngineConfig()):
    """shard_map PageRank: counted rounds of relax → exchange →
    rhizome-collapse(+) → damping update, dense or compact exchange, with
    the same fused-kernel hot path as the fixpoint apps."""
    from repro.core.actions import PAGERANK as sem

    cfg = _sharded_cfg(cfg, "make_sharded_pagerank_fn")
    axis_names = exchange.axis_tuple(axis_names)
    spec = P(axis_names)

    in_specs = (DeviceArrays.specs(spec),)
    base = (1.0 - damping) / n

    def shard_fn(arrays_l: DeviceArrays):
        arrays_s = jax.tree.map(lambda x: x[0], arrays_l)
        chg = arrays_s.slot_valid  # PR predicate is #t — always diffuse

        def gather(x):
            return lax.all_gather(x, axis_names, tiled=True)

        def body(_, val):
            total_in, _ = exchange.shard_total_in(
                sem, arrays_s, cfg, S, R_max, axis_names,
                gather(val), gather(chg))
            return jnp.where(arrays_s.slot_valid,
                             base + damping * total_in, 0.0)

        val0 = jnp.where(arrays_s.slot_valid, 1.0 / n, 0.0)
        val = lax.fori_loop(0, iters, body, val0)
        return val[None]

    fn = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs, out_specs=spec,
        check_vma=False,
    )
    return jax.jit(fn), NamedSharding(mesh, spec)


def run_pagerank_sharded(part: Partition, damping: float, iters: int,
                         mesh: Mesh, axis_names=("data", "model"),
                         cfg: EngineConfig = EngineConfig()):
    """shard_map PageRank execution; see ``run_sharded`` for layout."""
    fn, sharding = make_sharded_pagerank_fn(
        part.S, part.R_max, part.n, damping, iters, mesh, axis_names, cfg)
    arrays = DeviceArrays.from_partition(part)
    arrays_dev = jax.tree.map(lambda x: jax.device_put(x, sharding), arrays)
    with _dispatch("pagerank_sharded", 1):
        return fn(arrays_dev)


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def init_values(part: Partition, sem: Semiring, sources: dict[int, float]):
    """(S, R_max) initial values: semiring identity everywhere except all
    replicas of each source vertex (consistent initial view)."""
    val = np.full((part.S, part.R_max), sem.identity, dtype=np.float32)
    if sem.segment == "sum":
        val[:] = 0.0
    for v, x in sources.items():
        s0, sl0 = divmod(int(part.root_flat[v]), part.R_max)
        for k in range(part.cfg.rpvo_max):
            if part.sibling_mask[s0, sl0, k]:
                f = int(part.sibling_flat[s0, sl0, k])
                val[f // part.R_max, f % part.R_max] = x
    return val


def vertex_values(part: Partition, val) -> np.ndarray:
    """Extract the per-vertex (root-replica) values; reading a device
    array back is an ``engine.sync``."""
    if isinstance(val, jax.Array):
        with _sync("vertex_values", 0):
            val = np.asarray(val)
    gval = np.asarray(val).reshape(-1)
    return gval[part.root_flat]
