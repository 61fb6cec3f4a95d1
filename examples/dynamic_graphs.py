"""Dynamic graph processing (paper §7 future work): mutate the graph with
edge-insertion actions, then recompute BFS incrementally from the
affected region — without starting from scratch.

  PYTHONPATH=src python examples/dynamic_graphs.py
"""
import numpy as np

from repro.core.dynamic import DynamicGraph
from repro.core.partition import PartitionConfig
from repro.graph import generators, reference
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

g = generators.rmat(12, edge_factor=8, seed=3)
root = int(np.argmax(g.out_degrees()))
dg = DynamicGraph.build(g, PartitionConfig(num_shards=16, rpvo_max=8))

lv0, full_stats = dg.bfs_full(root)
print(f"initial BFS: {int(full_stats.iterations)} rounds, "
      f"{int(full_stats.messages)} messages")

# an action inserts shortcut edges (hub -> far vertices)
UNREACHED = np.iinfo(np.int32).max
reached = np.nonzero(lv0 != UNREACHED)[0]
far = reached[np.argsort(lv0[reached])[-8:]]
seeds = dg.insert_edges(np.full(far.shape, root, np.int32), far.astype(np.int32))
print(f"inserted {far.size} shortcut edges from the root")

lv1, inc_stats = dg.bfs_incremental_insert(seeds)
assert (lv1 == reference.bfs_levels(dg.g, root)).all()
improved = int((lv1[reached] < lv0[reached]).sum())
print(f"incremental BFS: {int(inc_stats.iterations)} rounds, "
      f"{int(inc_stats.messages)} messages "
      f"({100 * int(inc_stats.messages) / max(int(full_stats.messages), 1):.1f}% "
      f"of from-scratch), {improved} vertices improved — verified exact")

# deletions invalidate monotone state -> full recompute path
dg.delete_edges([int(g.src[0])], [int(g.dst[0])])
lv2, _ = dg.bfs_full(root)
assert (lv2 == reference.bfs_levels(dg.g, root)).all()
print("post-delete full recompute verified exact")
