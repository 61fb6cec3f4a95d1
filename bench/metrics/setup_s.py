"""Set-up seconds on the host clock: from the start of the process
(imports, JAX's start on the chip) through graph generation, partition
build, upload and the warm-up calls, to the opening of the window."""


def read(ctx):
    return ctx["setup"]["setup_s"]
