"""Runtime: 90th percentile, in ms, of a non-io node's wait from its first
sight in the ready pool to its launch, from the program's
``runtime.ready_wait`` spans that end in the window."""
import span_reduce


def read(ctx):
    if ctx.program_spans is None:
        return None
    return span_reduce.ready_wait_p90_ms(ctx.program_spans, ctx.window)
