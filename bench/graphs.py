"""The benchmark's own graph generators, vectorized and deterministic per
seed.  They follow the program's ``graph/generators.py`` semantics but are
kept here so that a change to the program cannot change the yardstick.

A graph is returned as ``(n, src, dst)``: int32 edge arrays, duplicates
removed, sorted by ``(src, dst)``.  Self loops are kept, as PaRMAT and
Graph500 keep them.
"""
from __future__ import annotations

import numpy as np


def _dedup(n: int, src: np.ndarray, dst: np.ndarray):
    key = np.unique(src.astype(np.int64) * n + dst.astype(np.int64))
    return (key // n).astype(np.int32), (key % n).astype(np.int32)


def rmat(scale: int, edge_factor: int, a: float, b: float, c: float,
         seed: int):
    """RMAT (PaRMAT): ``edge_factor * 2**scale`` draws, each picking one
    quadrant per bit with probabilities ``a, b, c, 1-a-b-c``; duplicates
    removed."""
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    src = np.zeros(m, np.int32)
    dst = np.zeros(m, np.int32)
    ab, abc = np.float32(a + b), np.float32(a + b + c)
    for _ in range(scale):
        r = rng.random(m, dtype=np.float32)
        src <<= 1
        dst <<= 1
        src |= r >= ab                                  # quadrants c, d
        dst |= ((r >= a) & (r < ab)) | (r >= abc)       # quadrants b, d
    src, dst = _dedup(n, src, dst)
    return n, src, dst


def erdos_renyi(scale: int, mean_degree: int, seed: int):
    """Directed Erdős–Rényi: ``mean_degree * 2**scale`` uniform draws of
    (src, dst); duplicates removed."""
    n = 1 << scale
    m = mean_degree * n
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=m, dtype=np.int32)
    dst = rng.integers(0, n, size=m, dtype=np.int32)
    src, dst = _dedup(n, src, dst)
    return n, src, dst


def generate(graph: dict, seed: int):
    """The configuration's ``graph`` section, made from ``seed``."""
    kind = graph["kind"]
    if kind == "rmat":
        return rmat(graph["scale"], graph["edge_factor"], graph["a"],
                    graph["b"], graph["c"], seed)
    if kind == "erdos_renyi":
        return erdos_renyi(graph["scale"], graph["mean_degree"], seed)
    raise ValueError(f"unknown graph kind {kind!r}")


def csr(n: int, src: np.ndarray, dst: np.ndarray):
    """(indptr, indices) of the out-adjacency; ``src`` must be sorted."""
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return indptr, dst
