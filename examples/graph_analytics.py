"""End-to-end driver: large(ish) skewed-graph analytics with the paper's
full pipeline — partition -> diffusive engine -> AM-CCA cost model —
comparing RPVO vs Rhizomatic-RPVO the way the paper's Figs 8/9 do.

  PYTHONPATH=src python examples/graph_analytics.py [--scale 14]
"""
import argparse
import time

import numpy as np

from repro.apps import bfs
from repro.core.costmodel import CostModel
from repro.core.partition import PartitionConfig, build_partition
from repro.graph import generators, reference
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--scale", type=int, default=13)
ap.add_argument("--shards", type=int, default=4096)
args = ap.parse_args()

g = generators.rmat(args.scale, edge_factor=16, seed=1)
root = int(np.argmax(g.out_degrees()))
print(f"RMAT-{args.scale}: V={g.n} E={g.num_edges}")

# real computation on the JAX engine (64-shard layout)
t0 = time.time()
levels, st, part = bfs(g, root, num_shards=64, rpvo_max=8)
print(f"engine BFS: {time.time()-t0:.1f}s, {int(st.iterations)} rounds, "
      f"levels verified={bool((levels == reference.bfs_levels(g, root)).all())}")

# paper-style chip-scale what-if: replay the frontier trace through the
# AM-CCA cost model at 64x64 cells, with and without rhizomes
trace = reference.bfs_frontier_trace(g, root)
for rmax, label in ((1, "rpvo"), (16, "rhizomatic")):
    p = build_partition(g, PartitionConfig(
        num_shards=args.shards, rpvo_max=rmax, local_edge_list_size=16))
    res = CostModel(p, torus=True).replay(trace)
    print(f"{label:12s} cells={args.shards}: est_cycles={res.cycles:9.0f} "
          f"max_link={res.max_link_load:6d} "
          f"energy={res.energy_pj/1e6:.1f} uJ")
