"""Share of the HBM roofline that the searches of the traced window
reach: the least bytes a level-synchronous BFS moves, over the chip's peak
HBM bandwidth, over the device's busy seconds in the window.

Least bytes.  A search reads, for every edge whose source it reached,
the edge's source and destination ids (4 B each), the source's level
(4 B) and writes or compares the destination's candidate (4 B): 16 B a
traversed edge.  Every round also reads and writes the vertex value
table once: 8 B a vertex a round.  Search work does a handful of integer
operations per 16 B, far below the chip's operations per byte (197 TFLOP/s
over 819 GB/s is about 240), so bandwidth, not compute, bounds it.
Edges and rounds are counted from the benchmark's reference levels, so
the number reads the same work whatever implements it.
"""

EDGE_BYTES = 16
VERTEX_ROUND_BYTES = 8


def least_bytes(traversed_edges: int, n: int, rounds: int) -> int:
    return EDGE_BYTES * traversed_edges + VERTEX_ROUND_BYTES * n * rounds


def read(ctx):
    trace, work = ctx["trace"], ctx["work"]
    if not trace or trace["busy_s"] <= 0 or "traversed_edges" not in work:
        return None
    least_s = least_bytes(work["traversed_edges"], work["n"],
                          work["rounds"]) / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
