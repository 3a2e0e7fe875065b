"""Models: model FLOPs of the tokens LM decode dispatches returned for
real members (2 x the role's parameters per token; pad rows and each
dispatch's re-prefill count for nothing), over the time some LM decode
dispatch was open (the union of their host spans) times the chip's bf16
peak, in percent."""
import flops
import trace_reduce


def read(ctx):
    roles = ctx.config["stage_roles"]
    spans = [s for s in ctx.spans if s.stage.endswith("_decode")]
    work = sum(flops.decode_flops(ctx.config["models"][roles[s.stage]],
                                  s.tokens) for s in spans)
    wall = sum(e - s for s, e in trace_reduce.union(
        [(s.t0, s.t1) for s in spans]))
    if ctx.peaks is None or not spans or wall <= 0 or work <= 0:
        return None
    return 100.0 * work / (wall * ctx.peaks["bf16_flops"])
