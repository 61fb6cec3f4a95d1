"""Traversed edges per second (Graph500 kernel 2): the edges whose source
each search of the window reached, counted from the benchmark's own
reference levels, summed over all searches, over the window's elapsed
seconds on the host clock."""


def read(ctx):
    edges = ctx["work"].get("traversed_edges")
    if edges is None:
        return None
    return edges / ctx["window"]["seconds"]
