"""Runtime: wall of the window's ``executor.run`` spans whose task was
cancelled (a straggler's copy that ran on) over the wall of all of them,
in percent."""
import span_reduce


def read(ctx):
    if ctx.program_spans is None:
        return None
    return span_reduce.wasted_dispatch_share(ctx.program_spans, ctx.window)
