"""90th percentile of the seconds from due to the end of the answer."""
from _latency import percentile


def read(ctx):
    return percentile(ctx, 90, lambda r: r.last)
