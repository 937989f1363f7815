"""Operations and bytes the benchmark's work needs, computed from shapes.

These are the yardstick of `mfu` and of every `<kernel>_roofline`: counts
of what the algorithm needs for the cell's shapes, never what the program
reports.  Each function says what it counts.  A roofline share is the
least time the chip could take, `max(flops / peak flops, bytes / peak
bandwidth)`, over the kernel's measured time, so counting too little keeps
a share honest and counting too much would push it past 100%.
"""
from __future__ import annotations

F32 = 4


def cohort_size(fl: dict) -> int:
    return max(int(round(fl["select_fraction"] * fl["num_clients"])), 1)


def lane_model_flops(model, shapes: dict, fl: dict, traffic: dict) -> float:
    """Client-model FLOPs of one lane's sweep, from the forward FLOPs per
    sample that ``model`` (a `bench/models` module) counts for ``shapes``.

    Forward and backward (3x forward) of every local SGD step of the
    cohort in every round, and of the warm-up bootstrap (one step of one
    batch on every client) where the traffic has it; plus the forward
    pass of the test set on every eval round.  k-means, sketches and the
    geometry are not counted.
    """
    fwd = model.train_flops(shapes)
    bs, n = fl["batch_size"], fl["samples_per_client"]
    steps = fl["local_epochs"] * max(n // bs, 1)
    rounds = traffic["rounds"]
    train = rounds * cohort_size(fl) * steps * bs * 3 * fwd
    if traffic["warmup"]:
        train += fl["num_clients"] * min(bs, n) * 3 * fwd
    return float(train + eval_rounds(traffic) * model.eval_samples(shapes)
                 * model.eval_flops(shapes))


def eval_rounds(traffic: dict) -> int:
    r, every = traffic["rounds"], max(traffic["eval_every"], 1)
    return sum(1 for i in range(r) if (i + 1) % every == 0 or i == r - 1)


def rttg_latency_cost(n: int, n_rsu: int, predict_steps: int, want_rid: bool):
    """(flops, bytes) of one geometry pass over ``n`` vehicles.

    Per vehicle: 9 ops per predictor step (0 steps on the realized pass),
    5 per RSU for the ring distance and the nearest-RSU choice, and 40 for
    the 3D distance, SNR, Shannon rate and latency terms (a transcendental
    counts as one).  Bytes: position, speed and acceleration read (fp32),
    latency written (fp32), the connected flag (1 byte) and, when asked
    for, the RSU id (int32).
    """
    flops = n * (9 * predict_steps + 5 * n_rsu + 40)
    nbytes = n * (3 * F32 + F32 + 1 + (F32 if want_rid else 0))
    return float(flops), float(nbytes)


# fp32 (P,) vectors a server rule reads and writes besides the update rows:
# params in and out, and the moments in and out where the rule keeps them
RULE_VECTORS = {"fedavg": 2, "fedbuff": 2, "fedavgm": 3, "fedadam": 6,
                "fedyogi": 6, "stale": 2}


def server_update_cost(rule: str, k: int, kb: int, p: int, itemsize: int):
    """(flops, bytes) of one lane's fused server step under ``rule``.

    The K cohort rows and, on a fedbuff lane, the Kb ring-buffer rows are
    read once; the parameter and moment vectors per ``RULE_VECTORS``.
    """
    rows = k + (kb if rule == "fedbuff" else 0)
    flops = 2 * rows * p + (10 * p if RULE_VECTORS[rule] == 6 else 2 * p)
    nbytes = rows * p * itemsize + rows * F32 + RULE_VECTORS[rule] * p * F32
    return float(flops), float(nbytes)


def roofline_share(flops: float, nbytes: float, seconds: float, peak: dict):
    """Least time over measured time, in %; None without a measured time."""
    if not seconds or seconds <= 0:
        return None
    least = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def n_rsu(scenario: dict) -> int:
    return max(int(scenario["ring_length_m"] / scenario["rsu_spacing_m"]), 1)


def predict_steps(scenario: dict) -> int:
    return max(int(round(scenario["predict_horizon_s"] / scenario["sim_dt_s"])), 1)

