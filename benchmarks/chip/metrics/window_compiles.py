"""Backend compiles inside the measured window (JAX's compile listener)."""


def read(ctx):
    return ctx.window_compiles
