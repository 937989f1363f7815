"""What decides `correct`: sampled lanes of the window against the reference.

After the window, `draw` picks one of its sweeps and orders its lanes, both
from the run's seed.  `compare` runs `bench/reference.py` on lanes from the
front of that order, each from its own experiment seed over the traffic's
first `check_rounds` rounds, until `check_lanes` of them have a decided
round, and reduces program and reference to numbers, each the worst over
those lanes and over the rounds the reference calls decided
(`reference.Metrics.decided`):

- `geometry_gap`: relative gap of the cohort's mean predicted and mean
  realized latency and of the round's duration: the twin and its advance,
  the fused observations, the predictor, the `rttg_latency` geometry, the
  election on the clusters of the warm-up and of each recluster, the
  mid-round advance and the deadline rule;
- `count_gap`: absolute gap of the counts (selected, succeeded, parked in
  and drained from the fedbuff ring);
- `loss_gap`: gap of the test loss on each eval round, over the reference's
  loss or the loss of a model that knows nothing, ln(10), whichever is
  larger: cohort training, the server reduce and rule, and the evaluation.
  (A lane that has learned its task reads a loss near 0 from large logits,
  whose rounding is absolute: measured against that loss alone, two
  correct computations part by any share.)

A round is decided when it and every round before it made each discrete
choice (RSU attachment, connectivity, the election, the clustering) by a
margin wider than two correct float32 computations in another order can
part.  From the first round that is not, a correct program may elect one
other vehicle, whose compute time and link change the round's duration
and the twin's course, so that round and the later ones are not compared
(see PERF.md).  Each number that bench/limits/<cell>.json gives a limit is
held to it.
"""
from __future__ import annotations

import dataclasses
import sys

import numpy as np

from bench import reference

COUNTS = ("n_selected", "n_succeeded", "n_buffered", "n_drained")
TEST_IMAGES = reference.N_TEST
LOSS_SCALE = float(np.log(reference.NUM_CLASSES))  # nats, an untrained model's loss


@dataclasses.dataclass
class Pick:
    seed: int  # the lane's experiment seed
    label: tuple  # (strategy, aggregator, seed, scenario)
    metrics: dict  # field -> (rounds,) array, as the program produced it


def draw(run_seed: int, done, lanes: int):
    """One sweep of ``done`` and all its lanes, in an order drawn from the
    seed: the check takes them from the front."""
    rng = np.random.default_rng(run_seed)
    s, res = done[int(rng.integers(len(done)))]
    m = {f: np.asarray(getattr(res.metrics, f)) for f in res.metrics._fields}
    return [Pick(s, tuple(res.runs[g]), {f: v[g] for f, v in m.items()})
            for g in rng.permutation(lanes)]


def references(cell: dict, picks):
    """(pick, its reference run) from the front of ``picks`` until
    `check_lanes` of them have a decided round, or the picks run out."""
    rounds, want = cell["mix"]["check_rounds"], cell["mix"]["check_lanes"]
    for p in picks:
        if want == 0:
            return
        ref = reference.run_lane(lane_of(cell, p.label), p.seed, rounds)
        want -= decided_rounds(ref, rounds) > 0
        yield p, ref


def lane_of(cell: dict, label: tuple):
    """The reference's `Lane` of a picked lane, in the configuration's
    precisions."""
    cfg, mix = cell["cfg"], cell["mix"]
    fl, shapes = cfg["fl"], cfg["shapes"]
    strategy, aggregator, _, scenario = label
    return reference.Lane(
        cfg["dataset"], tuple(shapes["image_shape"]), tuple(shapes["channels"]),
        shapes["d_ff"], tuple(sorted(fl.items())), tuple(mix["strategies"]),
        tuple(mix["aggregators"]), mix["rounds"], mix["eval_every"], mix["warmup"],
        fl["compute_dtype"], fl["param_dtype"], matmul=cfg["matmul_precision"],
        strategy=strategy, aggregator=aggregator, scenario=scenario,
    )


def _rel(a, b, floor=1e-30):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    fa, fb = np.isfinite(a), np.isfinite(b)
    if not np.array_equal(fa, fb):
        return float("inf")
    if not fb.any():
        return 0.0
    return float(np.max(np.abs(a[fb] - b[fb]) / np.maximum(np.abs(b[fb]), floor)))


def decided_rounds(ref: reference.Metrics, rounds: int) -> int:
    """How many of the first ``rounds`` rounds the reference decided."""
    return int(np.sum(np.asarray(ref.decided)[:rounds]))


def gaps(prog: dict, ref: reference.Metrics, rounds: int) -> dict:
    """The numbers of one lane (see the module docstring)."""
    n = decided_rounds(ref, rounds)
    cut = lambda x: np.asarray(x, np.float64)[:n]
    return {
        "geometry_gap": max(_rel(cut(prog[f]), cut(getattr(ref, f)))
                            for f in ("mean_pred_latency", "mean_real_latency",
                                      "duration")),
        "count_gap": float(max(np.max(np.abs(cut(prog[f]) - cut(getattr(ref, f))),
                                      initial=0.0)
                               for f in COUNTS)),
        "loss_gap": _rel(cut(prog["test_loss"]), cut(ref.test_loss), LOSS_SCALE),
    }


def detail(prog: dict, ref: reference.Metrics) -> dict:
    """Per field, the gap of each round: relative for the economics and the
    loss, absolute for the counts and the accuracy (in test images)."""
    out = {"decided": [bool(x) for x in ref.decided]}
    for f in ref._fields:
        if f == "decided":
            continue
        b = np.asarray(getattr(ref, f), np.float64)
        a = np.asarray(prog[f], np.float64)[:len(b)]
        if f in COUNTS:
            g = np.abs(a - b)
        elif f == "test_acc":
            g = np.abs(a - b) * TEST_IMAGES
        else:
            g = np.abs(a - b) / np.maximum(np.abs(b), 1e-30)
        out[f] = [float(x) for x in g]
    return out


def worst(per_lane):
    return {k: max(g[k] for g in per_lane) for k in per_lane[0]}


def compare(cell: dict, picks) -> dict:
    """{number: {"value", "limit"}} over the picked lanes, for each number
    the cell's limits hold."""
    rounds = cell["mix"]["check_rounds"]
    pairs = list(references(cell, picks))
    decided = [decided_rounds(ref, rounds) for _, ref in pairs]
    print(f"check: rounds decided of {rounds}, by lane: {decided}", file=sys.stderr)
    if not any(decided):
        raise SystemExit("bench: the reference decided no round of any lane")
    numbers = worst([gaps(p.metrics, ref, rounds) for p, ref in pairs])
    unknown = set(cell["limits"]) - set(numbers)
    if unknown:
        raise SystemExit(f"bench: limits for unknown numbers {sorted(unknown)}")
    return {k: {"value": numbers[k], "limit": v} for k, v in cell["limits"].items()}
