"""Shared arithmetic of the kernel metrics: device seconds per sweep on one
chip, and the cell's per-chip share of its lanes."""


def seconds_per_sweep(ctx, wrapper: str):
    """Mean over the chips of the kernel's device time per traced sweep."""
    devs = ctx["trace"]["devices"]
    total = sum(d["kernel_s"].get(wrapper, 0.0) for d in devs) / len(devs)
    return total / ctx["sweeps"] if total > 0 else None


def lanes_per_chip(ctx) -> float:
    return ctx["lanes"] / ctx["chips"]


def scenarios(ctx):
    """The scenario constants of each lane's road, one entry per scenario
    of the grid (every scenario has as many lanes as every other)."""
    return [ctx["scenarios"][name] for name in ctx["mix"]["scenarios"]]
