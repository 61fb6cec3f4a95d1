"""Plain float64 references for delta-PageRank traffic.  numpy only:
nothing here imports the program or takes anything it made.

* ``pagerank_converged``: the power iteration of ``bench.reference``
  (from 1/n, the mass of vertices without out-edges dropped) run until no
  score moves by ``rel`` times ``(1 - damping) / n`` or more in one
  iteration: the fixpoint that a delta-PageRank to a tolerance
  approximates.
* ``delta_rounds``: the push rounds of a delta-PageRank (Ligra's
  PageRankDelta): rank = delta = (1 - damping) / n; a round's frontier is
  ``|delta| > tol``; each frontier vertex pushes ``damping * delta /
  out_degree`` along its out-edges; residuals at or below ``tol`` are
  dropped, as is the mass of vertices without out-edges.  Its rounds and
  the out-edges of its frontiers count a call's work whatever implements
  it.

Edges come sorted by source, as ``bench.graphs`` makes them.
"""
from __future__ import annotations

import numpy as np


def _push_weights(n: int, src: np.ndarray):
    """(out_degree, 1 / out_degree with 0 where it is 0)."""
    if src.size and np.any(src[1:] < src[:-1]):
        raise ValueError("edges must be sorted by source")
    out_deg = np.bincount(src, minlength=n)
    inv = np.zeros(n)
    np.divide(1.0, out_deg, out=inv, where=out_deg > 0)
    return out_deg, inv


def _pull(n, dst, out_deg, per_vertex):
    """Sum over in-edges of the sources' ``per_vertex`` values."""
    return np.bincount(dst, weights=np.repeat(per_vertex, out_deg),
                       minlength=n)


def pagerank_converged(n: int, src: np.ndarray, dst: np.ndarray,
                       damping: float, rel: float = 1e-13,
                       max_iters: int = 10_000) -> np.ndarray:
    out_deg, inv = _push_weights(n, src)
    base = (1.0 - damping) / n
    score = np.full(n, 1.0 / n)
    for _ in range(max_iters):
        new = base + damping * _pull(n, dst, out_deg, score * inv)
        moved = np.abs(new - score).max()
        score = new
        if moved < rel * base:
            return score
    raise RuntimeError(f"power iteration did not converge in {max_iters}")


def delta_rounds(n: int, src: np.ndarray, dst: np.ndarray, damping: float,
                 tol: float, max_rounds: int):
    """(ranks, rounds run, out-edges of the frontiers summed over the
    rounds)."""
    out_deg, inv = _push_weights(n, src)
    rank = np.full(n, (1.0 - damping) / n)
    delta = rank.copy()
    rounds = frontier_edges = 0
    while rounds < max_rounds:
        live = np.abs(delta) > tol
        if not live.any():
            break
        frontier_edges += int(out_deg[live].sum())
        delta = damping * _pull(n, dst, out_deg,
                                np.where(live, delta * inv, 0.0))
        rank += delta
        rounds += 1
    return rank, rounds, frontier_edges
