"""Models: the MoE + MLA generator's decode steps, least time over device
time, in percent.  Device time is the ``jit_decode*`` ops inside the
generators' ``lm.call`` spans that lie wholly in the traced window (the
program's spans on the device trace's clock).  Least time is the bytes a
step must read at the chip's HBM bandwidth (``ref_mla_moe.
decode_step_bytes`` from the configuration's widths): every weight but the
embedding and the routed experts, the held experts the step's router
reached per MoE layer (the program's ``moe.experts_hit`` over
``moe.layer_steps`` in the window), and the latent cache positions the
call's real rows hold at that step.  Where the program counts no MoE
layer-steps there is nothing to read."""
import _spans
import ref_mla_moe
import span_reduce
import trace_reduce

DECODE = "jit_decode"


def read(ctx):
    counters = ctx.counters or {}
    layer_steps = counters.get("moe.layer_steps")
    clock = _spans.on_trace_clock(ctx)
    if not layer_steps or clock is None or ctx.peaks is None:
        return None
    _, off, lo, hi = clock
    hit = counters.get("moe.experts_hit", 0) / layer_steps
    models = ctx.config["models"]
    least, calls = 0.0, []
    for s in ctx.program_spans:
        role = s.attrs.get("role")
        if s.name != span_reduce.LM_CALL or role not in models \
                or "moe" not in models[role]:
            continue
        a = span_reduce.on_trace_clock(s.t0, off)
        b = span_reduce.on_trace_clock(s.t1, off)
        if a < lo or b > hi:
            continue
        calls.append((a, b))
        rows, prompt = s.attrs["rows"], s.attrs["prompt"]
        least += sum(ref_mla_moe.decode_step_bytes(
            models[role], hit, rows * (prompt + j))
            for j in range(1, s.attrs["steps"] + 1))
    decode = trace_reduce.union([(t, t + d) for evs in ctx.devices.values()
                                 for _, t, d, module in evs
                                 if module.startswith(DECODE)])
    device = span_reduce.length(span_reduce.intersect(
        decode, trace_reduce.union(calls))) * 1e-9
    if device <= 0 or least <= 0:
        return None
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / device
