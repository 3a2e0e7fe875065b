"""Set-up seconds on the host clock: process start to the end of the
warm-up query (backend start, build, store fill, stage programs)."""


def read(ctx):
    return ctx.setup_s
