"""Share of the HBM roofline that the delta-PageRank calls of the traced
window reach: the least bytes their rounds move, over the chip's peak HBM
bandwidth, over the device's busy seconds in the window.

Least bytes.  A round reads, for every out-edge of its frontier, the
edge's source and destination ids (4 B each), its weight 1/out-degree
(4 B) and its source's residual (4 B): 16 B a frontier edge.  It also
reads and writes the residual table once: 8 B a vertex a round.  The
frontier edges and the rounds are the benchmark's own float64 reference's
(``bench.reference_delta``), so the number reads the same work whatever
implements it; a round does two floating-point operations per 16 B, so
bandwidth, not compute, bounds it.  On a shrinking frontier this is the
worklist kernel's share of its roofline.
"""

EDGE_BYTES = 16
VERTEX_ROUND_BYTES = 8


def least_bytes(frontier_edges: int, n: int, rounds: int) -> int:
    return EDGE_BYTES * frontier_edges + VERTEX_ROUND_BYTES * n * rounds


def read(ctx):
    trace, work = ctx["trace"], ctx["work"]
    if not trace or trace["busy_s"] <= 0 or "ref_rounds" not in work:
        return None
    least_s = least_bytes(work["frontier_edges"], work["n"],
                          work["ref_rounds"]) / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
