"""Driver for search traffic: Graph500 kernel 2 style BFS from search
keys drawn from the seed, through ``repro.apps.bfs`` on a partition built
in set-up.

Traffic keys: ``roots``, the number of search keys, drawn at random
from ``--seed`` without replacement among the vertices of the giant
component (``reference.giant_core``; each has out-degree at least 1, as
Graph500 asks of a key) and searched in that order; the warm-up search
is one more draw.  ``checks.level_mismatches`` is the limit on vertices
whose level differs from the reference, over all searches of the window.
Keys in the giant component all reach the same vertices, so every key
traverses the same edges; a key drawn from the whole graph may sit in a
component of a few vertices.
"""
from __future__ import annotations

import numpy as np

from bench import graphs, reference


class Driver:
    def __init__(self, traffic: dict, n: int, src, dst, pcfg, ecfg,
                 seed: int):
        self.traffic, self.n, self.src, self.dst = traffic, n, src, dst
        self.pcfg, self.ecfg = pcfg, ecfg
        self.out_degree = np.bincount(src, minlength=n)
        core = reference.giant_core(n, src, dst)
        rng = np.random.default_rng([seed, 2])
        keys = rng.choice(core, size=min(traffic["roots"] + 1, core.size),
                          replace=False)
        self.roots, self.warm_root = keys[:-1], int(keys[-1])

    def build(self):
        from repro.core.partition import build_partition
        from repro.graph.graph import COOGraph
        self.g = COOGraph(self.n, self.src, self.dst, None)
        self.part = build_partition(self.g, self.pcfg)

    def _search(self, root: int):
        from repro import apps
        levels, _, _ = apps.bfs(self.g, root, part=self.part, cfg=self.ecfg)
        return levels

    def warm(self):
        self._search(self.warm_root)

    def call(self, i: int):
        root = int(self.roots[i % len(self.roots)])
        return root, self._search(root)

    def verify(self, answers):
        """Compare every search of the window with the reference; returns
        (checks, failed searches, work)."""
        indptr, indices = graphs.csr(self.n, self.src, self.dst)
        mismatches = failed = edges = rounds = 0
        for root, got in answers:
            want = reference.bfs_levels(self.n, indptr, indices, root)
            bad = reference.level_mismatches(got, want)
            mismatches += bad
            failed += bad > 0
            edges += reference.traversed_edges(self.out_degree, want)
            rounds += reference.bfs_rounds(want)
        checks = {"level_mismatches": {
            "value": mismatches,
            "limit": self.traffic["checks"]["level_mismatches"]}}
        work = {"n": self.n, "edges": int(self.src.size),
                "searches": len(answers), "traversed_edges": edges,
                "rounds": rounds}
        return checks, failed, work
