"""Stage fns: mean wall milliseconds of an embed, vsearch or rerank
dispatch, host entry to host exit."""

STAGES = ("embed", "vsearch", "rerank")


def read(ctx):
    d = [s.t1 - s.t0 for s in ctx.spans if s.stage in STAGES]
    return 1e3 * sum(d) / len(d) if d else None
