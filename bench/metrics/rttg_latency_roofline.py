"""`rttg_latency`'s share of its roofline, in %: the least time of the
operations and bytes its two geometry passes need per round
(`bench.flops.rttg_latency_cost`) over its device time."""
from bench import flops
from bench.metrics import _kernel


def read(ctx):
    s = _kernel.seconds_per_sweep(ctx, "_rttg_latency")
    if s is None:
        return None
    fl = ctx["cfg"]["fl"]
    n, rid = fl["num_clients"], bool(fl["hierarchical"])
    work = [0.0, 0.0]
    for sc in _kernel.scenarios(ctx):
        for cost in (flops.rttg_latency_cost(n, flops.n_rsu(sc), flops.predict_steps(sc), False),
                     flops.rttg_latency_cost(n, flops.n_rsu(sc), 0, rid)):
            work = [w + c for w, c in zip(work, cost)]
    scale = _kernel.lanes_per_chip(ctx) / len(ctx["mix"]["scenarios"]) * ctx["mix"]["rounds"]
    return flops.roofline_share(work[0] * scale, work[1] * scale, s, ctx["peak"])
