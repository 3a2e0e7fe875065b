"""Admission: 90th percentile of the milliseconds from a query's due time
to the entry of its first stage-fn call (session / admission layer)."""
import numpy as np


def read(ctx):
    first = {}
    for s in ctx.spans:
        for q in s.qids:
            first[q] = min(first.get(q, s.t0), s.t0)
    waits = [(first[r.qid] - r.due) * 1e3 for r in ctx.queries
             if r.qid in first]
    return float(np.percentile(waits, 90)) if waits else None
