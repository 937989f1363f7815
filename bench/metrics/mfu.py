"""Client-model FLOPs of the traced sweeps over (window x chips x the chip's
bf16 peak), in %.  The FLOPs come from the client model's counts for the
configuration's shapes (`bench.flops.lane_model_flops`): local training of
the cohorts and of the warm-up bootstrap, and the eval forward passes."""
from bench import flops


def read(ctx):
    cfg = ctx["cfg"]
    work = ctx["lanes"] * ctx["sweeps"] * flops.lane_model_flops(
        ctx["model"], cfg["shapes"], cfg["fl"], ctx["mix"])
    seconds = ctx["trace"]["window_s"] * ctx["chips"]
    return 100.0 * work / (seconds * ctx["peak"]["bf16_flops_per_s"]) if seconds else None
