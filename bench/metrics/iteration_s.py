"""Seconds per PageRank iteration: the window's elapsed seconds on the
host clock over all iterations of all calls completed in it."""


def read(ctx):
    iterations = ctx["work"].get("iterations")
    if not iterations:
        return None
    return ctx["window"]["seconds"] / iterations
