"""Scheduler: self time of the runtime's dispatch passes (reap, schedule,
launch) over the measured window's wall, in percent, from the program's
``runtime.dispatch_pass`` spans."""
import span_reduce


def read(ctx):
    if ctx.program_spans is None:
        return None
    return span_reduce.dispatch_pass_share(ctx.program_spans, ctx.window)
