"""Single-source shortest paths: min-plus diffusive relaxation.

Same action shape as BFS with ``msg = dist + w`` (paper §6: 'BFS and SSSP
actions take 2-3 cycles of compute').
"""
from __future__ import annotations

import numpy as np

from repro.core import actions, engine
from repro.core.partition import Partition, PartitionConfig, build_partition
from repro.graph.graph import COOGraph


@engine.app_span("sssp")
def sssp(g: COOGraph, root: int,
         part: Partition | engine.ResidentPartition | None = None,
         cfg: engine.EngineConfig = engine.EngineConfig(),
         num_shards: int = 16, rpvo_max: int = 1,
         mesh=None, axis_names=("data", "model")):
    """Returns (dist (n,) float64 with inf for unreachable, stats, partition)."""
    if part is None:
        part = build_partition(
            g, PartitionConfig(num_shards=num_shards, rpvo_max=rpvo_max)
        )
    init = engine.init_values(part, actions.SSSP, {root: 0.0})
    if mesh is None:
        val, stats = engine.run_stacked(actions.SSSP, part, init, cfg)
    else:
        val, stats = engine.run_sharded(
            actions.SSSP, part, init, mesh, axis_names, cfg
        )
    return engine.vertex_values(part, val).astype(np.float64), stats, part
