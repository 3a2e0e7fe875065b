"""Shared by the readers of the program's own spans (not a metric): the
device's busy intervals and the traced window on the trace's clock."""
import span_reduce


def on_trace_clock(ctx):
    """-> (busy intervals, offset s, traced window start ns, end ns), or
    None where the run recorded no spans, traced no device op, or matched
    no span to the trace."""
    if (ctx.program_spans is None or ctx.clock is None
            or ctx.trace_window is None):
        return None
    busy = span_reduce.busy(ctx.devices)
    if not busy:
        return None
    off = ctx.clock["offset_s"]
    lo, hi = (span_reduce.on_trace_clock(t, off) for t in ctx.trace_window)
    return busy, off, lo, hi
