"""Models: model FLOPs of the tokens LM decode dispatches returned for
real members, over the time some LM decode dispatch was open (the union
of their host spans) times the chip's bf16 peak, in percent.  A role's
FLOPs per token come from its configuration entry's reference module
(``decode_flops``) where it has them, else 2 x its dense parameters
(``flops.decode_flops``); pad rows and each dispatch's re-prefill count
for nothing."""
import flops
import harness
import trace_reduce


def decode_flops(m: dict, tokens: int) -> float:
    """Model FLOPs of ``tokens`` tokens of model entry ``m``."""
    count = getattr(harness.reference_module(m), "decode_flops",
                    flops.decode_flops)
    return count(m, tokens)


def read(ctx):
    roles, models = ctx.config["stage_roles"], ctx.config["models"]
    spans = [s for s in ctx.spans if s.stage.endswith("_decode")]
    work = sum(decode_flops(models[roles[s.stage]], s.tokens)
               for s in spans)
    wall = sum(e - s for s, e in trace_reduce.union(
        [(s.t0, s.t1) for s in spans]))
    if ctx.peaks is None or not spans or wall <= 0 or work <= 0:
        return None
    return 100.0 * work / (wall * ctx.peaks["bf16_flops"])
