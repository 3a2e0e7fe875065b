"""90th percentile of the seconds from due to the first answer token
group (the first ``chat_decode`` group streamed for the query)."""
from _latency import percentile


def read(ctx):
    return percentile(ctx, 90, lambda r: r.first)
