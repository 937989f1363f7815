"""The `server_update` kernel's share of its roofline, in %, as the fused
`server_update_buffered` step runs it: each lane's server step by that
lane's own rule (`bench.flops.server_update_cost`: the K cohort
rows, the Kb ring rows on fedbuff lanes, params and the rule's moments),
summed over the chip's lanes and rounds, over the kernel's device time."""
from bench import flops
from bench.metrics import _kernel


def read(ctx):
    s = _kernel.seconds_per_sweep(ctx, "server_update")
    if s is None:
        return None
    cfg, mix = ctx["cfg"], ctx["mix"]
    fl, p = cfg["fl"], cfg["shapes"]["params"]
    item = 2 if fl["compute_dtype"] == "bfloat16" else 4
    work = [0.0, 0.0]
    for rule in mix["aggregators"]:
        cost = flops.server_update_cost(rule, flops.cohort_size(fl), fl["buffer_size"], p, item)
        work = [w + c for w, c in zip(work, cost)]
    scale = _kernel.lanes_per_chip(ctx) / len(mix["aggregators"]) * mix["rounds"]
    return flops.roofline_share(work[0] * scale, work[1] * scale, s, ctx["peak"])
