"""Host seconds per round of the delta-PageRank calls of the traced
window: the window's seconds in which no operation ran on the device
(traced window less device busy time), over the rounds the program ran in
it (summed from its ``RunStats``).  What the host round loop costs a
round: the frontier download, the worklist plan, the dispatch and the
sync."""


def read(ctx):
    trace, rounds = ctx["trace"], ctx["work"].get("rounds")
    if not trace or trace["busy_s"] <= 0 or not rounds:
        return None
    return (trace["window_s"] - trace["busy_s"]) / rounds
