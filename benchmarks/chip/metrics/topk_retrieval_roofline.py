"""Kernels: the top-k search kernel's least time over its device time in
the trace, in percent.  The least time per search is the larger of its
FLOPs over the bf16 peak and its bytes (valid rows at the store's element
size, read once, plus the f32 query) over HBM bandwidth; the row count is
the mean the vsearch wrapper read during the traced window."""
import flops


def read(ctx):
    tr = ctx.trace
    if ctx.peaks is None or not tr or not tr["kernel_events"] or tr["kernel_s"] <= 0:
        return None
    t0, t1 = ctx.trace_window
    n = [s.n_valid for s in ctx.spans
         if s.stage == "vsearch" and s.t1 >= t0 and s.t0 <= t1]
    if not n:
        return None
    least, _ = flops.topk_least_time(sum(n) / len(n), ctx.store_dim, 1,
                                     ctx.store_itemsize,
                                     ctx.peaks)
    return 100.0 * least * tr["kernel_events"] / tr["kernel_s"]
