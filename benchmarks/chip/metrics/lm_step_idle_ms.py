"""Models: device idle time inside the ``lm.call`` spans that lie wholly in
the traced window, per ``lm.step`` of those calls, in ms (the program's
spans on the device trace's clock)."""
import _spans
import span_reduce


def read(ctx):
    clock = _spans.on_trace_clock(ctx)
    if clock is None:
        return None
    busy, off, lo, hi = clock
    return span_reduce.lm_step_idle_ms(ctx.program_spans, busy, off, lo, hi)
