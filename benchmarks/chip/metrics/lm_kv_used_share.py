"""Models: KV-cache bytes the window's LM calls wrote over the bytes they
allocated, in percent, from the program's ``lm.kv_bytes_*`` counters."""
import span_reduce


def read(ctx):
    if ctx.counters is None:
        return None
    return span_reduce.lm_kv_used_share(ctx.counters)
