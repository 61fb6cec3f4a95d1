"""Plain references for the comparison that decides ``correct``, and the
controls that the comparison has to catch.  numpy only: nothing here
imports the program or takes anything it made.

Semantics follow the program's ``graph/reference.py``:

* BFS levels from one root, ``UNREACHED`` (int32 max) where the root
  does not reach;
* PageRank from 1/n for a fixed number of iterations, each vertex
  sending ``score / out_degree`` along its out-edges, with the mass of
  vertices without out-edges dropped (the paper's Listing 10), in
  float64.
"""
from __future__ import annotations

import numpy as np

UNREACHED = np.iinfo(np.int32).max


def bfs_levels(n: int, indptr: np.ndarray, indices: np.ndarray, root: int,
               max_rounds: int | None = None) -> np.ndarray:
    """Level-synchronous BFS over a CSR out-adjacency.  ``max_rounds``
    stops the search after that many expansions (the control that breaks
    the exactness of levels); None runs to the fixpoint."""
    level = np.full(n, UNREACHED, np.int64)
    level[root] = 0
    frontier = np.array([root], np.int64)
    depth = 0
    while frontier.size and (max_rounds is None or depth < max_rounds):
        starts = indptr[frontier]
        counts = indptr[frontier + 1] - starts
        total = int(counts.sum())
        if total == 0:
            break
        offs = np.repeat(starts - np.cumsum(counts) + counts, counts)
        nbrs = indices[offs + np.arange(total)]
        nbrs = np.unique(nbrs[level[nbrs] == UNREACHED])
        depth += 1
        level[nbrs] = depth
        frontier = nbrs
    return level


def giant_core(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """The vertices of the strongly connected component that holds the
    vertex of highest out-degree (lowest id on ties): those that it
    reaches and that reach it, ascending.  On the benchmark's graphs this
    is the giant component, and every search key in it reaches the same
    vertices."""
    out_degree = np.bincount(src, minlength=n)
    hub = int(np.argmax(out_degree))
    order = np.argsort(dst, kind="stable")
    rev_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=n), out=rev_ptr[1:])
    fwd_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(out_degree, out=fwd_ptr[1:])
    down = bfs_levels(n, fwd_ptr, dst, hub) != UNREACHED
    up = bfs_levels(n, rev_ptr, src[order], hub) != UNREACHED
    return np.flatnonzero(down & up)


def traversed_edges(out_degree: np.ndarray, level: np.ndarray) -> int:
    """Graph500 kernel 2's count for a directed graph: the edges whose
    source the search reached."""
    return int(out_degree[level != UNREACHED].sum())


def bfs_rounds(level: np.ndarray) -> int:
    """Rounds a level-synchronous search takes: one per level, the last
    of which finds nothing new."""
    return int(level[level != UNREACHED].max()) + 1


def edge_weights(n: int, src: np.ndarray) -> np.ndarray:
    """``1 / out_degree(src)`` per edge, in float64."""
    out_deg = np.bincount(src, minlength=n)
    return 1.0 / out_deg[src]


def pagerank(n: int, src: np.ndarray, dst: np.ndarray, damping: float,
             iters: int) -> np.ndarray:
    """Power iteration with dropped dangling mass, in float64."""
    w = edge_weights(n, src)
    score = np.full(n, 1.0 / n)
    base = (1.0 - damping) / n
    for _ in range(iters):
        score = base + damping * np.bincount(dst, weights=score[src] * w,
                                             minlength=n)
    return score


def level_mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Vertices whose level differs from the reference's."""
    got = np.asarray(got)
    if got.shape != want.shape:
        return int(want.size)
    return int((got.astype(np.int64) != want).sum())


def max_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest ``|got - want| / want`` over vertices (every PageRank score
    is at least ``(1 - damping) / n`` > 0); inf where a value is missing
    or not finite."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float((np.abs(got - want) / want).max())
