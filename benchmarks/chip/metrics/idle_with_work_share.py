"""Device: share of the traced window in which no operation ran on the
chip while some due query was unanswered, in percent (the queries'
records placed on the device trace's clock by the program's spans)."""
import _spans
import span_reduce


def read(ctx):
    clock = _spans.on_trace_clock(ctx)
    if clock is None:
        return None
    busy, off, lo, hi = clock
    queue = span_reduce.waiting(ctx.queries, off, ctx.window[1])
    return span_reduce.idle_with_work_share(busy, queue, lo, hi)
