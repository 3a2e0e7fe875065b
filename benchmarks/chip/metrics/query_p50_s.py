"""Median seconds from a query's due time (scheduled arrival, or submit
in a closed loop) to the end of its answer stream, over every query due
in the window."""
from _latency import percentile


def read(ctx):
    return percentile(ctx, 50, lambda r: r.last)
