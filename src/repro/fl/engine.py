"""Batched FL experiment engine: (strategy x seed x scenario) grids on device.

The legacy loop runs ONE experiment at a time with a host round-trip every
round.  This engine runs a whole grid as a single XLA program:

  * each experiment is a ``lax.scan`` of the pure ``round_step`` over
    rounds (zero per-round host syncs; eval is a strided ``lax.cond``, and
    the re-clustering cadence rides the same xs stream so BOTH conds keep
    unbatched predicates — a genuine branch under vmap, not a both-sides
    select);
  * the grid axis is a ``vmap`` over (RoundState, ScenarioParams, strategy
    index, aggregator index, data row index), so strategies, server
    aggregation rules (``fl.aggregators.AGGREGATOR_ORDER``), seeds and
    scenarios batch together — a (strategy x aggregator x seed x scenario)
    grid is one program;
  * the scan carry (argument 0: stacked states / experiment keys) is
    DONATED to the compiled program (``donate_argnums``) and the carried
    model is the flat (P,) vector layout (``rounds.RoundState``), so
    steady-state sweeps update the grid's parameter matrix in place
    instead of re-laying it out every call;
  * given a device ``mesh``, the grid axis is SHARDED over it with
    ``shard_map`` (resolved through the ``"grid"`` rule in
    ``sharding.rules.TRAIN_RULES``, rows padded to the shard count and
    sliced back) — states, scenarios and the scan compute split across
    devices, so multi-device hosts and pods sweep hundreds of scenarios;
    falls back to the plain vmapped program whenever the mesh has a
    single device.  RoundData rows are SHARD-LOCAL: the host plans which
    dedup rows each shard's lanes gather (``partition.shard_local_rows``),
    ships each device only its own (M,) row seeds through the
    ``"data_rows"`` sharding rule, and remaps ``data_idx`` to shard-local
    positions — a seed-heavy grid's client-data footprint scales
    ~1/n_shards instead of replicating every row everywhere;
  * experiment INIT is device-resident too (``init_on_device=True``, the
    default): ``run_grid`` setup reduces to pure key stacking — the host
    folds one experiment key per row and the compiled program runs
    ``rounds.init_state_traced`` (model-param init + twin seeding) under
    the same vmap/shard_map, so host setup cost is independent of grid
    size and no parameter tree is ever allocated host-side (the round
    step's flat layout comes from a ``jax.eval_shape`` trace);
  * client shards are partitioned ON DEVICE inside the compiled program
    (``partition_on_device=True``, the default): ``rounds.make_round_data``
    materializes the (C, n, D) sample-row shards per unique data row under
    jit, so grid size is bounded by device memory, not host RAM;
  * the stacked rows are NEVER copied per lane: ``round_step`` gathers
    ``leaf[data_idx, ...]`` lazily at each use site (each SGD step gathers
    its batch as whole feature-minor rows by (data_idx, client, sample),
    a test-set gather only on eval rounds), so neither a per-lane shard
    copy nor a per-round cohort block is ever materialized;
  * per-round test evaluation is hoisted to every ``eval_every`` rounds
    (the final round always evaluates).

Shape conventions: the grid axis G is the LEADING dim of every stacked
leaf (experiment keys / states, scenario params, strategy indices,
metrics); ``RoundData`` rows are deduplicated to one per unique
(strategy, seed, ``scenarios.data_signature``) and gathered per lane by
``data_idx``.  Selection inside the round core is mask-based
and fixed-size; updates travel in the flat (K, P) layout (see
``repro.fl.rounds``).

Usage:

    eng = ExperimentEngine(model_cfg, fl_cfg, "mnist",
                           strategies=("contextual", "gossip"),
                           aggregators=("fedavg", "fedadam"),
                           mesh=make_grid_mesh())  # omit mesh on one device
    result = eng.run_grid(strategies=("contextual", "gossip"),
                          seeds=(0, 1), scenarios=("ring", "rush_hour"),
                          rounds=40, eval_every=5)
    result.records(strategy="contextual", seed=0, scenario="ring",
                   aggregator="fedadam")

Scenario names resolve through ``repro.core.scenarios``; passing explicit
``TrafficConfig`` objects also works as long as their static geometry
(vehicle count, RSU count) agrees across the grid.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.config import FLConfig, ModelConfig, TrafficConfig
from repro.fl.aggregators import validate_aggregators
from repro.core.scenarios import (
    ScenarioParams,
    data_signature,
    scenario_config,
    scenario_params,
    stack_scenarios,
)
from repro.fl.partition import shard_local_rows
from repro.fl.rounds import (
    RoundData,
    RoundMetrics,
    RoundState,
    RoundRecord,
    cohort_size_for,
    derive_regions,
    experiment_key,
    flat_spec_of,
    init_state,
    init_state_traced,
    make_round_data,
    make_round_step,
    make_warmup,
    metrics_to_records,
)
from repro.models import build_model
from repro.sharding import TRAIN_RULES, resolve_pspec, split_params
from repro.utils import tree_bytes
from repro.utils.tracing import span, stage

ScenarioLike = Union[str, TrafficConfig]


def _eval_flags(rounds: int, eval_every: int) -> jnp.ndarray:
    flags = [(r + 1) % max(eval_every, 1) == 0 or r == rounds - 1 for r in range(rounds)]
    return jnp.asarray(flags)


def _recluster_flags(rounds: int, recluster_every: int) -> jnp.ndarray:
    """Per-round re-cluster schedule, precomputed so the scan body's cond
    predicate stays UNBATCHED under vmap (see module docstring)."""
    every = max(recluster_every, 1)
    return jnp.asarray([(r + 1) % every == 0 for r in range(rounds)])


@dataclasses.dataclass
class GridResult:
    """Stacked metrics for a flat experiment grid.

    ``runs`` rows are (strategy, aggregator, seed, scenario name); the
    lookup helpers keep ``aggregator`` as a defaulted trailing keyword —
    omitted, it resolves to this result's SOLE aggregator, so
    single-aggregator grids (whatever the rule) read as before, and a
    multi-aggregator lookup that omits it fails with the axis values
    rather than an opaque ``list.index`` miss.
    """

    metrics: RoundMetrics  # leaves (G, rounds)
    runs: List[Tuple[str, str, int, str]]  # (strategy, aggregator, seed, scenario)

    def _resolve_aggregator(self, aggregator: Optional[str]) -> str:
        if aggregator is not None:
            return aggregator
        axis = sorted({r[1] for r in self.runs})
        if len(axis) != 1:
            raise ValueError(
                "this grid swept multiple aggregators — pass aggregator= "
                f"explicitly (one of: {', '.join(axis)})"
            )
        return axis[0]

    def index_of(self, strategy: str, seed: int, scenario: str,
                 aggregator: Optional[str] = None) -> int:
        aggregator = self._resolve_aggregator(aggregator)
        return self.runs.index((strategy, aggregator, seed, scenario))

    def records(self, strategy: str, seed: int, scenario: str,
                aggregator: Optional[str] = None) -> List[RoundRecord]:
        g = self.index_of(strategy, seed, scenario, aggregator)
        one = jax.tree_util.tree_map(lambda x: x[g], self.metrics)
        return metrics_to_records(one)

    def final_accuracy(self) -> Dict[Tuple[str, str, int, str], float]:
        acc = np.asarray(self.metrics.test_acc)
        return {run: float(acc[g, -1]) for g, run in enumerate(self.runs)}


class ExperimentEngine:
    """Compiles one program per (rounds, grid-shape) and reuses it.

    ``mesh``: optional ``jax.sharding.Mesh``; when its axes named by the
    ``"grid"`` sharding rule span > 1 device, ``run_grid`` shards the grid
    axis over them (``launch.mesh.make_grid_mesh()`` builds the all-device
    1-D mesh).  ``partition_on_device``: build client shards inside the
    compiled program (default) instead of stacking host copies.
    ``aggregators``: the server-optimizer registry slice this engine
    compiles (``fl.aggregators.AGGREGATOR_ORDER`` names); the default
    single-``fedavg`` registry traces the frozen pre-registry path.

    ``last_data_plan`` (after a sharded ``run_grid``): the shard-local
    RoundData placement — ``{"total_rows", "rows_per_shard", "n_shards"}``
    — exposed for tests and capacity planning; ``None`` on the vmapped
    path (one device holds every dedup row by definition).
    """

    def __init__(
        self,
        model_cfg: ModelConfig,
        fl_cfg: FLConfig,
        dataset: str,
        strategies: Sequence[str] = ("contextual",),
        num_clients: Optional[int] = None,
        mesh=None,
        partition_on_device: bool = True,
        init_on_device: bool = True,
        aggregators: Sequence[str] = ("fedavg",),
        warmup: bool = True,
    ):
        if num_clients is not None:
            fl_cfg = dataclasses.replace(fl_cfg, num_clients=num_clients)
        self.fl = fl_cfg
        # ``warmup=False`` skips the deadline-rule bootstrap (which trains
        # every one of the N clients once): the fleet-scale hierarchical
        # path can't afford an all-N pass, and cluster-free strategies
        # never read the warm sketches anyway
        self.warmup_enabled = bool(warmup)
        self.dataset = dataset
        self.strategies = tuple(strategies)
        self.aggregators = validate_aggregators(aggregators)
        self.api = build_model(model_cfg)
        self.cohort_size = cohort_size_for(fl_cfg, self.strategies)
        self.mesh = mesh
        self.partition_on_device = partition_on_device
        # device-resident init needs device-resident data (regions are a
        # twin-init by-product); host data stacking implies host init
        self.init_on_device = bool(init_on_device and partition_on_device)
        self._round_step = None
        self.last_data_plan = None
        # donate the stacked states / experiment keys: the scan carry is
        # consumed by the program, so XLA updates the grid's flat parameter
        # matrix in place instead of re-laying it out every sweep
        self._grid_fn = jax.jit(
            self._grid, static_argnames=("warm",), donate_argnums=(0,)
        )
        self._sharded_fn = None  # built lazily once the padded spec is known

    # -- lazy build: model bytes / flat spec need a concrete param tree ----
    def _init_params(self, key):
        """key -> plain-array params pytree (the traced model init)."""
        return split_params(self.api.init(key))[0]

    def _ensure_step(self, params):
        if self._round_step is None:
            self.model_bytes = float(tree_bytes(params))
            self.param_spec = flat_spec_of(params)
            self._round_step = make_round_step(
                self.api.loss, self.fl, self.cohort_size, self.model_bytes,
                self.param_spec, strategies=self.strategies,
                aggregators=self.aggregators,
            )
            self._warmup = make_warmup(self.api.loss, self.fl, self.param_spec)
        return self._round_step

    def _ensure_spec(self):
        """Build the round step from an abstract model-init trace.

        The device-resident setup path never initializes params on the host
        — per-row init happens inside the compiled grid program — but the
        compiled step needs the parameter byte count and flat layout, which
        only depend on shapes: ``jax.eval_shape`` traces the init without
        allocating a single parameter.  Host work is therefore independent
        of grid size (the host-allocation test counts init calls).
        """
        if self._round_step is None:
            self._ensure_step(
                jax.eval_shape(self._init_params, jax.random.key(0))
            )

    def _traffic_of(self, scenario: ScenarioLike) -> TrafficConfig:
        if isinstance(scenario, TrafficConfig):
            tc = scenario
        else:
            tc = scenario_config(scenario, num_vehicles=self.fl.num_clients)
        if tc.num_vehicles != self.fl.num_clients:
            raise ValueError(
                "every FL client is a CAV: num_clients "
                f"({self.fl.num_clients}) must equal num_vehicles "
                f"({tc.num_vehicles})"
            )
        return tc

    def init_run(self, strategy: str, seed: int, scenario: ScenarioLike):
        """Host-side build of one grid row: (state, data, scn, strategy_idx).

        The legacy (``init_on_device=False``) path: params + twin are
        initialized eagerly per row.  ``data`` is a full ``RoundData`` on
        the host-partition path, or the tiny (key, regions) seed the
        compiled program expands on device.  The default engine never calls
        this — ``run_grid`` stacks experiment keys and the compiled program
        runs ``init_state_traced`` itself.
        """
        tc = self._traffic_of(scenario)
        self._ensure_spec()  # flat layout comes from the abstract trace
        state, regions = init_state(
            self.api, self.fl, tc, self.dataset, strategy, jax.random.key(seed)
        )
        if self.partition_on_device:
            data = (state.key, regions)
        else:
            data = make_round_data(state.key, self.dataset, self.fl, regions)
        # local index into this engine's strategy tuple (the switch carries
        # only those branches), not the global STRATEGY_ORDER
        return state, data, scenario_params(tc), self.strategies.index(strategy)

    # -- grid-axis sharding ------------------------------------------------
    def grid_shards(self) -> int:
        """How many ways the mesh's grid-rule axes split the grid dim."""
        if self.mesh is None:
            return 1
        sizes = dict(self.mesh.shape)
        n = 1
        for a in TRAIN_RULES.get("grid") or ():
            n *= sizes.get(a, 1)
        return n

    def _build_sharded(self, row: PartitionSpec, data_spec: PartitionSpec):
        """One shard_map program: each device runs the vmapped scan on its
        slice of grid rows against ONLY its own shard-local RoundData rows
        (``data_spec`` splits the (n_shards * M) row axis); the tiny eval /
        recluster flag streams replicate."""
        rep = PartitionSpec()

        def fn(states, datas, scns, strat_idx, agg_idx, data_idx, flags):
            def local(states, datas, scns, strat_idx, agg_idx, data_idx, flags):
                return self._grid(
                    states, datas, scns, strat_idx, agg_idx, data_idx, flags,
                    warm=self.warmup_enabled,
                )

            return jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(row, data_spec, row, row, row, row, rep),
                out_specs=(row, row),
                check_vma=False,
            )(states, datas, scns, strat_idx, agg_idx, data_idx, flags)

        return jax.jit(fn, donate_argnums=(0,))

    # -- the single compiled program --------------------------------------
    def _materialize(self, datas) -> RoundData:
        """Expand on-device data seeds into stacked RoundData rows (no-op on
        the host path).  Runs inside jit: one traced partition per unique
        data row — never a host-materialized copy.  Under the sharded
        engine the seeds arriving here are already the device's SHARD-LOCAL
        slice, so each device expands only the rows its lanes gather.

        Two seed forms: ``(keys, regions)`` (host init computed the regions
        eagerly) and ``(keys, ScenarioParams)`` (device-resident init: the
        (C,) home regions are re-derived from the twin spawn inside the
        program, so the host never touches a vehicle position either).
        """
        if isinstance(datas, RoundData):
            return datas
        keys, aux = datas
        if isinstance(aux, ScenarioParams):
            def one(k, scn):
                return make_round_data(
                    k, self.dataset, self.fl, derive_regions(k, scn)
                )

            return jax.vmap(one)(keys, aux)
        return jax.vmap(
            lambda k, r: make_round_data(k, self.dataset, self.fl, r)
        )(keys, aux)

    def _init_states(self, states, scns):
        """Stacked initial RoundStates — built in-program under device init.

        ``states`` is either the host-stacked RoundState pytree (legacy
        path, returned as-is) or the (G,) stacked experiment keys: one
        vmapped ``init_state_traced`` then folds model-param init + twin
        seeding into the compiled grid program, so ``run_grid`` setup is
        pure key stacking.
        """
        if isinstance(states, RoundState):
            return states
        return jax.vmap(
            lambda k, scn: init_state_traced(
                self._init_params, self.fl, scn, k
            )[0]
        )(states, scns)

    def _grid(self, states, datas, scns, strat_idx, agg_idx, data_idx, flags,
              warm: bool = True):
        # ``datas`` is unbatched (in_axes=None): rows differing only by
        # scenario share byte-identical client shards + test sets (the
        # experiment key folds strategy/seed/dataset, never the scenario;
        # platoon spawn regroups regions, so its rows carry their own
        # ``data_signature``), so it holds one row per unique signature and
        # each lane gathers from its row by ``data_idx`` — not one per grid
        # cell, and never as a per-lane materialized copy (round_step
        # indexes the stacked rows lazily at each use site).
        with stage("init"):
            states = self._init_states(states, scns)
            datas = self._materialize(datas)
        step = self._round_step

        def one(state, scn, si, ai, di):
            if warm:
                with stage("warmup"):
                    state = self._warmup(state, datas, di)

            def body(s, xs):
                do_eval, do_recluster = xs
                # tag the scan body so hlo_analysis can trip-weight the
                # per-round ops (the ``round-step`` target)
                with jax.named_scope("round"):
                    return step(s, scn, si, ai, datas, do_eval, do_recluster, di)

            final, metrics = jax.lax.scan(body, state, flags)
            return final, metrics

        return jax.vmap(one, in_axes=(0, 0, 0, 0, 0))(
            states, scns, strat_idx, agg_idx, data_idx
        )

    def run_grid(
        self,
        seeds: Sequence[int],
        scenarios: Sequence[ScenarioLike],
        rounds: int,
        strategies: Optional[Sequence[str]] = None,
        aggregators: Optional[Sequence[str]] = None,
        eval_every: int = 1,
    ) -> GridResult:
        """Run the (strategy x aggregator x seed x scenario) grid as one
        program."""
        strategies = tuple(strategies) if strategies is not None else self.strategies
        unknown = set(strategies) - set(self.strategies)
        if unknown:
            raise ValueError(
                f"strategies {sorted(unknown)} not covered by this engine's "
                f"cohort size; construct it with strategies={sorted(set(self.strategies) | unknown)}"
            )
        aggregators = (
            tuple(aggregators) if aggregators is not None else self.aggregators
        )
        unknown = set(aggregators) - set(self.aggregators)
        if unknown:
            raise ValueError(
                f"aggregators {sorted(unknown)} not in this engine's compiled "
                f"registry; construct it with "
                f"aggregators={sorted(set(self.aggregators) | unknown)}"
            )
        runs = list(itertools.product(strategies, aggregators, seeds, scenarios))
        states, scn_list, sidx, aidx = [], [], [], []
        data_rows, data_row_of, didx = [], {}, []
        with span("lanes", lanes=len(runs)):
            for strategy, aggregator, seed, scenario in runs:
                tc = self._traffic_of(scenario)
                if self.init_on_device:
                    # pure key stacking: model init + twin seeding + client
                    # partitioning all happen inside the compiled grid program
                    self._ensure_spec()
                    st = experiment_key(self.dataset, strategy, seed)
                    scn = scenario_params(tc)
                    si = self.strategies.index(strategy)
                    da = (st, scn)
                else:
                    st, da, scn, si = self.init_run(strategy, seed, scenario)
                states.append(st)
                scn_list.append(scn)
                sidx.append(si)
                aidx.append(self.aggregators.index(aggregator))
                # client shards/test set depend on (strategy, seed) plus the
                # spawn-layout signature (platoon regroups regions) — NEVER the
                # aggregator (a server-side rule over the same data streams);
                # keep one stacked row per unique triple (see _grid)
                pair = (strategy, seed, data_signature(tc))
                if pair not in data_row_of:
                    data_row_of[pair] = len(data_rows)
                    data_rows.append(da)
                didx.append(data_row_of[pair])
        stack = lambda *xs: jnp.stack(xs)

        def stack_rows(rows, order=None):
            """Stack dedup data rows (optionally gathered in ``order``)."""
            rows = [rows[i] for i in order] if order is not None else rows
            if self.init_on_device:
                return (
                    jnp.stack([k for k, _ in rows]),
                    stack_scenarios([s for _, s in rows]),
                )
            return jax.tree_util.tree_map(stack, *rows)

        G = len(runs)
        nsh = self.grid_shards()
        self.last_data_plan = None
        sharded = False
        with span("stack"):
            if self.init_on_device:
                states = jnp.stack(states)
            else:
                states = jax.tree_util.tree_map(stack, *states)
            scns = stack_scenarios(scn_list)
            strat_idx = jnp.asarray(sidx, jnp.int32)
            agg_idx = jnp.asarray(aidx, jnp.int32)
            data_idx = np.asarray(didx, np.int32)
            flags = (_eval_flags(rounds, eval_every),
                     _recluster_flags(rounds, self.fl.recluster_every))
            if nsh > 1:
                # pad grid rows to the shard count (repeating the last row),
                # shard the leading axis, slice the metrics back afterwards
                pad = (-G) % nsh
                if pad:
                    pad_idx = np.concatenate([np.arange(G), np.full(pad, G - 1)])
                    take = lambda x: x[pad_idx]
                    states = jax.tree_util.tree_map(take, states)
                    scns = jax.tree_util.tree_map(take, scns)
                    strat_idx, agg_idx = strat_idx[pad_idx], agg_idx[pad_idx]
                    data_idx = data_idx[pad_idx]
                spec = resolve_pspec(("grid",), (G + pad,), self.mesh, TRAIN_RULES)
                # a spec that does not split the grid (should not happen
                # after padding) falls back to the vmapped program
                sharded = len(spec) > 0 and spec[0] is not None
            if sharded:
                # shard-local RoundData: ship each device only the dedup
                # rows its lanes gather, remap data_idx to local positions
                shard_rows, data_idx = shard_local_rows(data_idx, nsh)
                M = shard_rows.shape[1]
                datas = stack_rows(data_rows, order=shard_rows.reshape(-1))
                self.last_data_plan = {
                    "total_rows": len(data_rows),
                    "rows_per_shard": M,
                    "n_shards": nsh,
                }
                dspec = resolve_pspec(
                    ("data_rows",), (nsh * M,), self.mesh, TRAIN_RULES
                )
                if self._sharded_fn is None:
                    self._sharded_fn = self._build_sharded(
                        PartitionSpec(spec[0]), PartitionSpec(dspec[0])
                    )
            else:
                datas = stack_rows(data_rows)
            data_idx = jnp.asarray(data_idx)
        with span("launch"):
            if sharded:
                _, metrics = self._sharded_fn(
                    states, datas, scns, strat_idx, agg_idx, data_idx, flags,
                )
            else:
                _, metrics = self._grid_fn(
                    states, datas, scns, strat_idx, agg_idx, data_idx, flags,
                    warm=self.warmup_enabled,
                )
            if nsh > 1:
                metrics = jax.tree_util.tree_map(lambda x: x[:G], metrics)
        scenarios = list(scenarios)

        def _label(sc):
            return sc if isinstance(sc, str) else f"custom-{scenarios.index(sc)}"

        labels = [(strategy, aggregator, seed, _label(sc))
                  for strategy, aggregator, seed, sc in runs]
        return GridResult(metrics=metrics, runs=labels)

    def run_single(
        self,
        strategy: str,
        seed: int,
        scenario: ScenarioLike = "ring",
        rounds: int = 40,
        eval_every: int = 1,
        aggregator: Optional[str] = None,
    ) -> List[RoundRecord]:
        """One experiment through the same scan program (grid of size 1)."""
        result = self.run_grid(
            seeds=(seed,), scenarios=(scenario,), rounds=rounds,
            strategies=(strategy,),
            aggregators=(aggregator or self.aggregators[0],),
            eval_every=eval_every,
        )
        return metrics_to_records(
            jax.tree_util.tree_map(lambda x: x[0], result.metrics)
        )
