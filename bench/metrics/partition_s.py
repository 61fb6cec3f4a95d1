"""Seconds of the program's partition build (``build_partition``) in
set-up, on the host clock."""


def read(ctx):
    return ctx["setup"]["partition_s"]
