"""FL-over-C-ITS experiment driver (the paper's §IV runs).

  PYTHONPATH=src python -m repro.launch.fl_sim --dataset mnist \
      --strategy contextual --rounds 60 --connection-rate 1.0 \
      --classes-per-client 2 --out artifacts/fl/mnist_contextual.json

``--scenario`` selects any entry of the ``repro.core.scenarios`` catalog —
steady densities (ring / highway / urban_grid), the time-varying
``rush_hour`` / ``day_cycle`` schedules, infrastructure-failure
``rsu_outage``, convoy-correlated ``platoon`` and compute-tier
``hetero_fleet`` families (see docs/scenarios.md).  ``--aggregator``
selects the server optimizer from the ``repro.fl.aggregators`` registry
(fedavg / fedavgm / fedadam / fedyogi / staleness-discounted ``stale``).
``--dtype bfloat16`` turns on the mixed-precision lane (bf16 compute/comm
against an fp32 master — docs/performance.md "Precision").  An unknown
name for any of the three fails fast with the registered catalog.
Whole (strategy x aggregator x seed x scenario) sweeps should use
``repro.fl.engine.ExperimentEngine`` directly: it batches the grid into
one device-resident program and shards it over a mesh when given one.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax

from repro.config import FLConfig
from repro.configs import get_config
from repro.configs.paper_models import PAPER_MODEL_BY_DATASET
from repro.core.scenarios import SCENARIOS, scenario_config
from repro.core.selection import STRATEGIES
from repro.fl.aggregators import AGGREGATOR_ORDER
from repro.fl.simulation import FLSimulation, time_to_accuracy
from repro.launch.compile_cache import use_compile_cache


def run_experiment(
    dataset: str,
    strategy: str,
    rounds: int,
    connection_rate: float = 1.0,
    classes_per_client: int = 2,
    num_clients: int = 100,
    seed: int = 0,
    local_epochs: int | None = None,
    samples_per_client: int = 256,
    time_budget_s: float | None = None,
    verbose: bool = False,
    predict_horizon_s: float | None = None,
    scenario: str = "ring",
    aggregator: str = "fedavg",
    dtype: str = "float32",
):
    if scenario not in SCENARIOS:
        raise ValueError(
            f"unknown scenario {scenario!r}; registered catalog: "
            f"{', '.join(sorted(SCENARIOS))} (see docs/scenarios.md to add one)"
        )
    if aggregator not in AGGREGATOR_ORDER:
        raise ValueError(
            f"unknown aggregator {aggregator!r}; registered catalog: "
            f"{', '.join(AGGREGATOR_ORDER)} (see repro/fl/aggregators.py)"
        )
    if dtype not in FLConfig.SUPPORTED_DTYPES:
        raise ValueError(
            f"unknown dtype {dtype!r}; supported dtypes: "
            f"{', '.join(FLConfig.SUPPORTED_DTYPES)} "
            f"(see docs/performance.md \"Precision\")"
        )
    model_cfg = get_config(PAPER_MODEL_BY_DATASET[dataset])
    # paper §IV-A: 3 local epochs on MNIST, 1 on CIFAR-10/SVHN
    epochs = local_epochs if local_epochs is not None else (3 if dataset == "mnist" else 1)
    fl = FLConfig(
        num_clients=num_clients,
        local_epochs=epochs,
        connection_rate=connection_rate,
        classes_per_client=classes_per_client,
        samples_per_client=samples_per_client,
        num_clusters=10,
        aggregator=aggregator,
        seed=seed,
        compute_dtype=dtype,
    )
    tr = scenario_config(scenario, num_vehicles=num_clients)
    if predict_horizon_s is not None:
        # ablation: horizon ~0 selects on the CURRENT fused RTTG (stage 2 off)
        tr = dataclasses.replace(tr, predict_horizon_s=predict_horizon_s)
    sim = FLSimulation(model_cfg, fl, tr, dataset, strategy, jax.random.key(seed))
    history = sim.run(rounds, time_budget_s=time_budget_s, verbose=verbose)
    return {
        "dataset": dataset,
        "strategy": strategy,
        "aggregator": aggregator,
        "connection_rate": connection_rate,
        "scenario": scenario,
        "classes_per_client": classes_per_client,
        "num_clients": num_clients,
        "seed": seed,
        "dtype": dtype,
        "rounds": [dataclasses.asdict(r) for r in history],
        "time_to_acc_0.5": time_to_accuracy(history, 0.5),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="mnist", choices=sorted(PAPER_MODEL_BY_DATASET))
    ap.add_argument("--strategy", default="contextual", choices=sorted(STRATEGIES))
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--connection-rate", type=float, default=1.0)
    # no argparse ``choices``: the catalog errors below list the registered
    # names themselves (and stay correct for programmatic run_experiment calls)
    ap.add_argument("--scenario", default="ring")
    ap.add_argument("--aggregator", default="fedavg")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--classes-per-client", type=int, default=2)
    ap.add_argument("--num-clients", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--time-budget", type=float, default=None)
    ap.add_argument("--out", default="")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    if args.scenario not in SCENARIOS:
        ap.error(
            f"unknown scenario {args.scenario!r}; registered catalog: "
            f"{', '.join(sorted(SCENARIOS))}"
        )
    if args.aggregator not in AGGREGATOR_ORDER:
        ap.error(
            f"unknown aggregator {args.aggregator!r}; registered catalog: "
            f"{', '.join(AGGREGATOR_ORDER)}"
        )
    if args.dtype not in FLConfig.SUPPORTED_DTYPES:
        ap.error(
            f"unknown dtype {args.dtype!r}; supported dtypes: "
            f"{', '.join(FLConfig.SUPPORTED_DTYPES)}"
        )

    use_compile_cache()
    result = run_experiment(
        args.dataset, args.strategy, args.rounds, args.connection_rate,
        args.classes_per_client, args.num_clients, args.seed,
        time_budget_s=args.time_budget, verbose=not args.quiet,
        scenario=args.scenario, aggregator=args.aggregator,
        dtype=args.dtype,
    )
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {args.out}")
    print(f"time-to-0.5-acc: {result['time_to_acc_0.5']}")


if __name__ == "__main__":
    main()
