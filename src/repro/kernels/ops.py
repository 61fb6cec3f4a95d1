"""Jit'd public wrappers for the Pallas kernels.

The one place that decides how a kernel runs: compiled by Mosaic on a
TPU backend, interpreted (the kernel body evaluated by XLA on the host)
everywhere else — CPU test runs set ``JAX_PLATFORMS=cpu`` and get the
interpreter.  The launchers themselves take ``interpret`` as a required
argument, so no caller can fall into interpret mode by default.
"""
from __future__ import annotations

import jax

from repro.kernels.fused_relax_reduce import (
    fused_relax_reduce_lanes_pallas, fused_relax_reduce_pallas,
)
from repro.kernels.rhizome_segment_reduce import segment_combine_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def segment_combine(data, segment_ids, num_segments: int, kind: str):
    """Semiring segment reduction (min | sum) over edge messages."""
    return segment_combine_pallas(
        data, segment_ids, num_segments, kind, interpret=_interpret()
    )


def fused_relax_reduce(gval, gchg, edge_src, edge_w, edge_mask, edge_dst,
                       num_segments: int, relax_kind: str, kind: str,
                       worklist=None, smem_budget_bytes=None,
                       grid_mode: str = "dense"):
    """Fused frontier gather + semiring relax + mask + segment reduction —
    the whole per-round relax phase in one launch.  Returns
    ((num_segments,) partial, active-edge message count).  A host-planned
    ``worklist`` (see ``fused_relax_reduce.WorklistPlanner``) swaps the
    dense early-exit grid for the 1-D live-cell launch;
    ``grid_mode='device_worklist'`` compacts the live-cell list on device
    instead (traced — works inside jit/shard_map loops);
    ``smem_budget_bytes`` arms the scalar-prefetch table guard."""
    return fused_relax_reduce_pallas(
        gval, gchg, edge_src, edge_w, edge_mask, edge_dst, num_segments,
        relax_kind, kind, interpret=_interpret(), with_count=True,
        worklist=worklist, smem_budget_bytes=smem_budget_bytes,
        grid_mode=grid_mode
    )


def fused_relax_reduce_lanes(gval, gchg, lane_unitw, edge_src, edge_w,
                             edge_mask, edge_dst, num_segments: int,
                             relax_kind: str, kind: str, worklist=None,
                             smem_budget_bytes=None,
                             grid_mode: str = "dense"):
    """Lane-batched fused relax phase: per-lane (V, Q) values/frontiers
    over one shared edge structure, one launch for all queries.  Returns
    ((num_segments, Q) partial, (Q,) per-lane active-edge counts).
    ``worklist`` (planned over the OR-across-lanes frontier) selects the
    live-cell launch, and ``grid_mode='device_worklist'`` compacts that
    list on device."""
    return fused_relax_reduce_lanes_pallas(
        gval, gchg, lane_unitw, edge_src, edge_w, edge_mask, edge_dst,
        num_segments, relax_kind, kind, interpret=_interpret(),
        with_count=True, worklist=worklist,
        smem_budget_bytes=smem_budget_bytes, grid_mode=grid_mode
    )
