"""Scheduler coalescing: real member streams per LM decode dispatch."""


def read(ctx):
    w = [s.width for s in ctx.spans if s.stage.endswith("_decode")]
    return sum(w) / len(w) if w else None
