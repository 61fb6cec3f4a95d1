"""Driver for delta-PageRank traffic: PageRank run to a tolerance by push
residual propagation (the mass of vertices without out-edges is dropped,
as the paper's Listing 10 does), through ``repro.apps.pagerank_delta`` on
a partition built in set-up from edge weights ``1 / out_degree`` and held
on the device across calls (``repro.core.engine.ResidentPartition``).  A
program without that object cannot run the cell, and fails in
``build``, before the partition is built.

Traffic keys: ``damping``; ``tol_rel``, the tolerance as a share of the
teleport term, ``tol = tol_rel * (1 - damping) / n``; ``max_rounds``, the
rounds a call may take; ``checks.max_rel_err``, the limit on the largest
relative error of any vertex's score, over all calls of the window,
against the converged float64 power iteration; ``checks.rounds_rel_err``,
the limit on the same error against the float64 push rounds at the same
tolerance (``bench.reference_delta.delta_rounds``), which leaves out the
method's own truncation and so catches smaller faults of the rounds;
``checks.unconverged_calls``, the limit on calls that ran all
``max_rounds`` rounds.

``verify`` reports the window's work from the benchmark's own float64
rounds (``bench.reference_delta``), so that it reads the same whatever
implements them: ``ref_rounds``, the reference's rounds times the calls;
``frontier_edges``, the out-edges of the reference's frontiers summed over
its rounds and the calls; ``iterations``, the dense iterations' worth of
work of the calls, ``(ref_rounds + frontier_edges / edges) / 2``; and
``rounds``, the program's own rounds, summed from its ``RunStats``.

Why ``iterations`` weighs rounds and frontier edges alike.  A round has
a part that every round pays, whatever its frontier (the frontier test,
the round trip to the host, and on the device every edge's gather), and
a part that follows its frontier's edges (the plan and the worklist
launch).  The reference's rounds depend on the seed: on ``rmat20`` a
graph in which some vertex's only out-edge is a self-loop keeps that
vertex live for ~30 more rounds (78-84 against 49), for the same 46.2
dense iterations' worth of frontier edges.  So neither count alone
follows a call's seconds; on a v5e the two parts cost about the same
(0.28 s a round and 0.30 s a dense iteration's edges), and a round with
every edge live counts one iteration, as in the fixed-iteration cells.
"""
from __future__ import annotations

import numpy as np

from bench import reference, reference_delta


class Driver:
    def __init__(self, traffic: dict, n: int, src, dst, pcfg, ecfg,
                 seed: int):
        self.traffic, self.n, self.src, self.dst = traffic, n, src, dst
        self.pcfg, self.ecfg = pcfg, ecfg
        self.damping = traffic["damping"]
        self.tol = traffic["tol_rel"] * (1.0 - self.damping) / n
        self.max_rounds = traffic["max_rounds"]

    def build(self):
        from repro.core.engine import ResidentPartition
        from repro.core.partition import build_partition
        from repro.graph.graph import COOGraph
        w = reference.edge_weights(self.n, self.src).astype(np.float32)
        self.g = COOGraph(self.n, self.src, self.dst, w)
        self.part = ResidentPartition(build_partition(self.g, self.pcfg))

    def call(self, i: int = 0):
        """(scores, rounds the program ran)."""
        from repro import apps
        scores, stats, _ = apps.pagerank_delta(
            self.g, self.damping, self.tol, part=self.part, cfg=self.ecfg,
            max_rounds=self.max_rounds)
        return scores, int(stats.iterations)

    def warm(self):
        self.call()

    def verify(self, answers):
        """Compare every call of the window with the reference; returns
        (checks, failed calls, work)."""
        want = reference_delta.pagerank_converged(
            self.n, self.src, self.dst, self.damping)
        ranks, rounds, frontier_edges = reference_delta.delta_rounds(
            self.n, self.src, self.dst, self.damping, self.tol,
            self.max_rounds)
        limits = self.traffic["checks"]
        errs = [reference.max_rel_err(got, want) for got, _ in answers]
        round_errs = [reference.max_rel_err(got, ranks)
                      for got, _ in answers]
        unconverged = [r >= self.max_rounds for _, r in answers]
        checks = {
            "max_rel_err": {"value": max(errs, default=float("inf")),
                            "limit": limits["max_rel_err"]},
            "rounds_rel_err": {"value": max(round_errs,
                                            default=float("inf")),
                               "limit": limits["rounds_rel_err"]},
            "unconverged_calls": {"value": sum(unconverged),
                                  "limit": limits["unconverged_calls"]}}
        failed = sum(not e <= limits["max_rel_err"]
                     or not q <= limits["rounds_rel_err"] or u
                     for e, q, u in zip(errs, round_errs, unconverged))
        calls, edges = len(answers), int(self.src.size)
        work = {"n": self.n, "edges": edges, "calls": calls,
                "iterations": calls * (rounds + frontier_edges / edges) / 2,
                "ref_rounds": rounds * calls,
                "frontier_edges": frontier_edges * calls,
                "rounds": sum(r for _, r in answers)}
        return checks, failed, work
