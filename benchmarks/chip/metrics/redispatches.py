"""Runtime: straggler re-dispatches plus retried stage failures in the
window's event timelines."""


def read(ctx):
    return ctx.redispatches
