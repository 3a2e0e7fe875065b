"""Stage fns: mean wall milliseconds of an LM decode dispatch (rewrite,
plan, refine, chat), host entry to host exit."""


def read(ctx):
    d = [s.t1 - s.t0 for s in ctx.spans if s.stage.endswith("_decode")]
    return 1e3 * sum(d) / len(d) if d else None
