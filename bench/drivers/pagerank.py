"""Driver for fixed-iteration PageRank traffic (LDBC Graphalytics
PageRank with a fixed iteration count; the mass of vertices without
out-edges is dropped, as the paper's Listing 10 does), through
``repro.apps.pagerank`` on a partition built in set-up from edge weights
``1 / out_degree``.

Traffic keys: ``iters`` and ``damping`` of each call,
``checks.max_rel_err`` (the limit on the largest relative error of any
vertex's score, over all calls of the window, against the float64
reference).
"""
from __future__ import annotations

import numpy as np

from bench import reference


class Driver:
    def __init__(self, traffic: dict, n: int, src, dst, pcfg, ecfg,
                 seed: int):
        self.traffic, self.n, self.src, self.dst = traffic, n, src, dst
        self.pcfg, self.ecfg = pcfg, ecfg

    def build(self):
        from repro.core.partition import build_partition
        from repro.graph.graph import COOGraph
        w = reference.edge_weights(self.n, self.src).astype(np.float32)
        self.g = COOGraph(self.n, self.src, self.dst, w)
        self.part = build_partition(self.g, self.pcfg)

    def call(self, i: int = 0):
        from repro import apps
        scores, _ = apps.pagerank(self.g, self.traffic["damping"],
                                  self.traffic["iters"], part=self.part,
                                  cfg=self.ecfg)
        return scores

    def warm(self):
        self.call()

    def verify(self, answers):
        """Compare every call of the window with the reference; returns
        (checks, failed calls, work)."""
        want = reference.pagerank(self.n, self.src, self.dst,
                                  self.traffic["damping"],
                                  self.traffic["iters"])
        errs = [reference.max_rel_err(got, want) for got in answers]
        limit = self.traffic["checks"]["max_rel_err"]
        checks = {"max_rel_err": {"value": max(errs, default=float("inf")),
                                  "limit": limit}}
        failed = sum(not e <= limit for e in errs)
        work = {"n": self.n, "edges": int(self.src.size),
                "calls": len(answers),
                "iterations": len(answers) * self.traffic["iters"]}
        return checks, failed, work
