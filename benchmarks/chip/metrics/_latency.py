"""Shared arithmetic of the latency readers (not a metric)."""
import numpy as np


def percentile(ctx, q, start):
    """q-th percentile over answered due queries of (end - due), where
    ``start`` picks the end time of a query."""
    vals = [start(r) - r.due for r in ctx.queries if r.answered]
    return float(np.percentile(vals, q)) if vals else None
