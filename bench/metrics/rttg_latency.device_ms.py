"""Device time of the `rttg_latency` Pallas kernel per sweep on one chip,
in ms (both geometry passes of every round of the chip's lanes)."""
from bench.metrics import _kernel


def read(ctx):
    s = _kernel.seconds_per_sweep(ctx, "_rttg_latency")
    return None if s is None else 1e3 * s
