"""Readings that set the limits of `correct`, from one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 [--control] [--faults frozen,half]

For each of ``--seeds`` sweeps of the cell (seeds drawn as a run draws
them), the lanes a run would check are run through `bench/reference.py`.
Each prints the numbers of `bench/check.py` for the program, and with
``--control`` for the two controls, one precision below what the
configuration states: `control`, the program itself with its matrix
products one precision lower (`highest` -> `high`, three bf16 passes, on
the same seeds), and `control_reference`, the reference in the program's
place with its matrix products as low and its twin and radio in bfloat16.  ``--faults`` does the same for the reference with a planted
fault: ``frozen`` (each round hands its state on unchanged) or ``half``
(half the cohort left out of the mean).  The last line is a JSON summary:
the largest reading of the program and the smallest of each control and
fault, per number.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import check, reference  # noqa: E402
from bench import run as bench_run  # noqa: E402

BELOW = {"highest": "high"}  # the matmul precision one step down


def readings(cell, devices, seeds: int, base_seed: int, control: bool, faults,
             detail: bool = False):
    """-> {"program" | "control" | fault: [per-lane numbers, ...]}."""
    mix = cell["mix"]
    eng = bench_run.build_engine(cell, devices)
    low_eng = None
    if control:
        below = BELOW[cell["cfg"]["matmul_precision"]]
        low = {**cell, "cfg": {**cell["cfg"], "matmul_precision": below}}
        low_eng = bench_run.build_engine(low, devices)
    rounds = mix["check_rounds"]
    scale = check.loss_scale(cell)
    test_samples = cell["model"].eval_samples(cell["cfg"]["shapes"])
    out = {"program": []}
    for i in range(seeds):
        t0 = time.perf_counter()
        s = bench_run.sweep_seed(base_seed, i)
        res = bench_run.sweep(eng, cell, s)
        jax.block_until_ready(res.metrics)
        t1 = time.perf_counter()
        picks = check.draw(base_seed + i, [(s, res)], bench_run.lanes_of(mix))
        low_picks = {}
        if low_eng is not None:
            low_res = bench_run.sweep(low_eng, low, s)
            low_picks = {p.label: p for p in check.draw(
                base_seed + i, [(s, low_res)], bench_run.lanes_of(mix))}
        t_ref = time.perf_counter()
        for p, ref in check.references(cell, picks):
            lane = check.lane_of(cell, p.label)
            lp = low_picks.get(p.label)
            t2 = time.perf_counter()
            ref_s, t_ref = t2 - t_ref, t2
            runs = {"program": p.metrics}
            if lp is not None:
                runs["control"] = lp.metrics
            if control:
                runs["control_reference"] = reference.run_lane(
                    lane._replace(matmul=below, geometry_dtype="bfloat16"), p.seed,
                    rounds)._asdict()
            for f in faults:
                runs[f] = reference.run_lane(lane._replace(fault=f), p.seed,
                                             rounds)._asdict()
            row = {k: check.gaps(m, ref, rounds, scale) for k, m in runs.items()}
            for k, v in row.items():  # a lane with no decided round reads nothing
                if check.decided_rounds(ref, rounds):
                    out.setdefault(k, []).append(v)
            print(json.dumps({"seed": p.seed, "lane": p.label, "sweep_s": t1 - t0,
                              "reference_s": ref_s,
                              "decided": check.decided_rounds(ref, rounds), **row}),
                  flush=True)
            if detail:
                print(json.dumps({"detail": {k: check.detail(m, ref, test_samples)
                                             for k, m in runs.items()}}), flush=True)
    return out


def summary(out: dict) -> dict:
    worst = {"program": check.worst(out["program"])}
    for k, rows in out.items():
        if k != "program":
            worst[k] = {n: min(r[n] for r in rows if n in r) for n in rows[0]}
    return worst


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seed", type=int, default=1_000_003)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    ap.add_argument("--detail", action="store_true",
                    help="also print each field's gap in each round")
    args = ap.parse_args(argv)
    cell = bench_run.load_cell(args.workload)
    bench_run.use_compile_cache()
    devices = bench_run.check_device(cell["chips"])
    faults = [f for f in args.faults.split(",") if f]
    out = readings(cell, devices, args.seeds, args.seed, args.control, faults,
                   args.detail)
    print(json.dumps({"summary": summary(out)}))


if __name__ == "__main__":
    main()
