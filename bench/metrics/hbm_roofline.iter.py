"""Share of the HBM roofline that the PageRank calls of the traced window
reach: the least bytes a push PageRank iteration moves, over the chip's
peak HBM bandwidth, over the device's busy seconds in the window.

Least bytes.  An iteration reads, for every edge, its source and
destination ids (4 B each), its weight 1/out-degree (4 B) and its
source's score (4 B): 16 B an edge.  It also reads and writes the score
table once: 8 B a vertex.  An iteration does two floating-point
operations per edge, far below the chip's operations per byte (197
TFLOP/s over 819 GB/s is about 240), so bandwidth, not compute, bounds
it.  Edges and vertices are the graph's, so the number reads the same
work whatever implements it.
"""

EDGE_BYTES = 16
VERTEX_BYTES = 8


def least_bytes(edges: int, n: int, iterations: int) -> int:
    return (EDGE_BYTES * edges + VERTEX_BYTES * n) * iterations


def read(ctx):
    trace, work = ctx["trace"], ctx["work"]
    if not trace or trace["busy_s"] <= 0 or "iterations" not in work:
        return None
    least_s = least_bytes(work["edges"], work["n"], work["iterations"]) \
        / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
