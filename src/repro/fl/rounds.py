"""Pure functional FL round core: one jitted program per round.

This is the device-resident heart of the experiment engine.  The legacy
``FLSimulation.run_round`` interleaved host numpy (``np.nonzero`` cohort
gathers, ``ok.any()`` branching, python ``round()`` step counts) with jitted
stages, forcing a host sync + dispatch every round.  Here the selector's
four pipeline stages (fusion -> prediction -> clustering -> election), the
cohort training, the realized-latency round economics and the FedAvg update
are folded into a single pure function

    round_step(state, scn, strategy_idx, aggregator_idx, data, do_eval, ...)
        -> (state, metrics)

with *fixed-size, mask-based* selection (no data-dependent shapes) and
``jnp.where``/``lax.cond`` branching, so a whole experiment is one
``lax.scan`` and a (strategy x seed x scenario) grid is one ``vmap`` of it
(see ``repro.fl.engine``).  Strategies are traced via ``lax.switch`` over
``STRATEGY_ORDER`` so the strategy axis vmaps like any other.

One-sweep geometry (default ``fused=True``): both per-round geometry
passes — the stage-2 *predicted* chain (fusion -> horizon prediction ->
RSU attach -> latency -> connectivity) and the mid-round *realized* chain
— run through the fused ``rttg_latency`` kernel path
(``kernels.ops.rttg_latency_auto``), one tiled (N-block x R) sweep per
pass instead of five-plus separate jnp sweeps plus an (N, N) adjacency the
selector never reads.  ``fused=False`` keeps the legacy composition of the
same core pure forms; the two paths are BITWISE identical (the guard in
tests/test_round_fused.py runs them against each other with the kernel in
interpret mode).

Aggregation runs on the *flat* update layout through the Pallas
``fedavg_reduce`` kernel (one HBM sweep of the (K, P) update matrix),
rather than K pytree AXPYs — and the carried global model IS that flat
(P,) fp32 vector: the scan carry is a single buffer the jit donates
(``fl.engine``), the FedAvg delta lands as one AXPY, and the pytree view
is materialized only where a consumer needs it (trainer, eval).

The server UPDATE RULE is a registry axis (``fl.aggregators``,
``AGGREGATOR_ORDER``): ``round_step`` takes a traced ``aggregator_idx``
alongside ``strategy_idx``, the first/second-moment server state rides the
carry as two more flat (P,) vectors (``RoundState.opt_m`` / ``opt_v``),
and the reduce + moment rules + parameter step run as ONE fused P-blocked
pass (``kernels.ops.server_update_auto``).  FedAvg weights come from the
per-client sample counts carried in ``RoundData.counts`` (bitwise-equal to
the old ``fl.samples_per_client`` constant while partitioners fill every
slot); the ``stale`` rule replaces the hard deadline drop with a
staleness discount of the realized per-client round time
(``aggregators.staleness_scale``) — the rule itself only redirects the
model update, never the round physics, so round ECONOMICS (duration,
deadline payments, selection) stay identical across aggregator lanes
until the deadline rule's re-clustering first consumes sketches computed
from the diverged models (cluster-dependent strategies may then elect
different cohorts; cluster-free strategies like gossip/greedy/network
keep identical economics indefinitely).  A single-``fedavg`` registry
with ``fedprox_mu=0``
traces the pre-registry reduce+AXPY path line for line, so that branch
stays bitwise-frozen (tests/test_aggregators.py holds it against the
general switch path in both dispatch modes).

Shape conventions (docs/architecture.md has the full walkthrough):

  * N = num_clients, K = cohort_size (static; selection is a length-N
    bool MASK compacted into K slots, never a data-dependent gather);
  * client updates travel as the FLAT (K, P) layout (``flat_spec_of``
    round-trips the pytree) until the single FedAvg reduction;
  * ``RoundData`` rows may carry a leading dedup-row axis: passing
    ``data_idx`` makes every access gather ``leaf[data_idx, ...]`` lazily
    at the use site (each SGD step's batch by ``(data_idx, client,
    sample)``), so the batched engine shares one stacked row set across
    lanes without materializing per-lane copies;
  * every ``RoundState``/``RoundData``/``RoundMetrics`` leaf gains a
    LEADING grid axis (G, ...) under the batched engine — per-experiment
    code never indexes it, ``vmap``/``shard_map`` insert it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.config import FLConfig, TrafficConfig
from repro.core.fusion import fuse_kinematics, fuse_messages
from repro.core.messages import emit_cams, emit_cpms
from repro.core.network import connectivity, latency_model
from repro.core.rttg import build_rttg, n_rsu_of, rsu_up_mask
from repro.core.selection import STRATEGIES
from repro.core.clustering import (
    apply_sketch,
    kmeans_cluster,
    sketch_sign_vector,
)
from repro.core.trajectory import predict_rttg
from repro.core.twin import advance_twin, init_twin_state
from repro.fl.aggregators import (
    AGGREGATOR_ORDER,
    FEDBUFF_IDX,
    STALE_IDX,
    init_opt_vectors,
    server_hp,
    staleness_scale,
    validate_aggregators,
)
from repro.fl.client import make_local_trainer
from repro.fl.partition import client_sample_counts, make_test_set, partition_clients
from repro.fl.server import (
    apply_delta_flat,
    normalized_weights,
    rsu_normalized_weights,
)
from repro.kernels.ops import (
    fedavg_reduce_auto,
    pick_block_p,
    rsu_reduce_auto,
    rttg_latency_auto,
    server_update_auto,
    server_update_buffered_auto,
)
from repro.sharding import split_params
from repro.utils import flatten_to_vector, fold_in_str, unflatten_from_vector
from repro.utils.tracing import stage

# lax.switch branch order: the traced strategy axis indexes this tuple.
STRATEGY_ORDER: Tuple[str, ...] = ("greedy", "gossip", "data", "network", "contextual")

# FLConfig dtype NAMES -> jnp dtypes (the config module stays jax-free;
# FLConfig.__post_init__ rejects anything outside this set by name)
_PRECISIONS = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def precision_of(fl: FLConfig) -> Tuple[Any, Any]:
    """Resolve the config's precision axis -> (param_dtype, compute_dtype).

    ``param_dtype`` is the master model carry (``RoundState.params``);
    ``compute_dtype`` the client-update / comm lane — the (K, P) delta
    vectors, the (Kb, P) fedbuff ring and the (R, P) chunk partials.  The
    server moments ``opt_m``/``opt_v`` stay fp32 regardless: they are the
    accumulator the adaptive rules integrate over, never a comm payload.
    Both default to fp32, in which case every gate below is static-off and
    the traced program is the historical one.
    """
    pd = _PRECISIONS[getattr(fl, "param_dtype", "float32")]
    cd = _PRECISIONS[getattr(fl, "compute_dtype", "float32")]
    return jnp.dtype(pd), jnp.dtype(cd)

# Twin integration inside the round core splits every advance into this many
# equal sub-steps (static trip count): under vmap no grid lane lock-steps on
# the slowest lane's round duration, and the scan body stays while-loop-free.
ADVANCE_SUBSTEPS = 15


class RoundState(NamedTuple):
    """Everything a round mutates, as one device-resident pytree.

    ``params`` is the FLAT (P,) model vector in the MASTER dtype
    (``FLConfig.param_dtype``, fp32 by default — see module docstring);
    ``opt_m`` / ``opt_v`` the server optimizer's first/second-moment
    vectors in the same flat layout, ALWAYS fp32 (zeros at init; plain
    fedavg carries them untouched); ``sketch_sign`` is a per-experiment
    constant (the
    Rademacher projection signs) carried here so the rounds scan never
    re-draws a P-long Bernoulli — XLA cannot hoist it out of the scan
    body on its own.

    The ``buf_*`` leaves are the FedBuff-style in-flight delta ring buffer
    (the ``fedbuff`` aggregator lane): ``Kb = FLConfig.buffer_size`` fixed
    slots holding the raw update vectors of deadline-missing stragglers,
    plus per-slot arrival time (absolute sim seconds), dispatch time (the
    staleness base), sample-count weight and an occupancy mask.  All
    fixed-shape and mask-based, so they join the donated scan carry and
    vmap/shard like every other leaf; lanes running any other rule carry
    them through as inert zeros.
    """

    params: jax.Array  # (P,) flat global model vector (FLConfig.param_dtype)
    opt_m: jax.Array  # (P,) server first-moment state (fl.aggregators; fp32)
    opt_v: jax.Array  # (P,) server second-moment state (fp32)
    twin: TwinState  # ground-truth traffic state
    sketches: jax.Array  # (N, sketch_dim) update sketches (stage 3)
    sketch_age: jax.Array  # (N,) rounds since last report
    clusters: jax.Array  # (N,) int32 data-cluster labels
    sketch_sign: jax.Array  # (P padded,) Rademacher signs (per-experiment const)
    buf_delta: jax.Array  # (Kb, P) in-flight straggler deltas (fedbuff;
    #     FLConfig.compute_dtype — the comm-lane payload precision)
    buf_arrive: jax.Array  # (Kb,) f32 absolute arrival sim_time per slot
    buf_sent: jax.Array  # (Kb,) f32 dispatch sim_time (staleness base)
    buf_weight: jax.Array  # (Kb,) f32 sample-count weight at dispatch
    buf_mask: jax.Array  # (Kb,) bool slot occupancy
    round: jax.Array  # () int32 completed-round counter
    sim_time: jax.Array  # () f32 cumulative simulated seconds
    key: jax.Array  # per-experiment base PRNG key (never advanced)


class RoundData(NamedTuple):
    """Per-experiment constants: client shards + global test set.

    ``counts`` carries each client's usable-sample count: FedAvg weights
    read THIS (not the ``fl.samples_per_client`` constant), so a
    partitioner that fills clients unevenly weights them honestly.
    """

    images: jax.Array  # (N, n, D) sample rows, D = H*W*C features minor
    labels: jax.Array  # (N, n)
    counts: jax.Array  # (N,) f32 per-client sample counts (FedAvg weights)
    test_x: jax.Array
    test_y: jax.Array


class RoundMetrics(NamedTuple):
    """Per-round telemetry; scan stacks these along the rounds axis."""

    round: jax.Array
    sim_time: jax.Array
    duration: jax.Array
    n_selected: jax.Array
    n_succeeded: jax.Array
    n_buffered: jax.Array  # int32: stragglers parked in the fedbuff buffer
    n_drained: jax.Array  # int32: buffer slots landed in this server step
    mean_pred_latency: jax.Array
    mean_real_latency: jax.Array
    test_acc: jax.Array
    test_loss: jax.Array


@dataclasses.dataclass
class RoundRecord:
    """Host-side view of one round (the legacy public record type)."""

    round: int
    sim_time: float  # cumulative simulated seconds at round END
    duration: float
    n_selected: int
    n_succeeded: int
    mean_pred_latency: float
    mean_real_latency: float
    test_acc: float
    test_loss: float
    n_buffered: int = 0  # fedbuff: stragglers parked this round
    n_drained: int = 0  # fedbuff: buffer slots landed this round


def cohort_size_for(fl: FLConfig, strategies: Sequence[str]) -> int:
    """Static training-cohort width covering every strategy in the grid.

    Greedy trains every connected client, so any grid containing it pays
    the full-width cohort; the top-k strategies never exceed ``n_select``.
    """
    return fl.num_clients if "greedy" in strategies else fl.n_select


def flat_spec_of(params) -> Any:
    """Spec matching ``flatten_to_vector``'s layout, without materializing."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    return (treedef, [x.shape for x in leaves], [x.dtype for x in leaves])


def flat_size_of(param_spec) -> int:
    """Total flat fp32 vector length of a ``flat_spec_of`` spec."""
    _, shapes, _ = param_spec
    return sum(int(functools.reduce(lambda a, b: a * b, s, 1)) for s in shapes)


def experiment_key(dataset: str, strategy: str, seed: int) -> jax.Array:
    """The per-experiment base PRNG key (``RoundState.key``).

    Folds strategy + dataset into the seed's key — NEVER the scenario, so
    rows differing only by scenario share data streams (the engine's
    RoundData dedup relies on this).  This fold is the ONLY host-side
    per-row work the device-resident engine setup does: ``run_grid`` stacks
    these keys and everything else happens inside the compiled program.
    """
    return fold_in_str(jax.random.key(seed), f"fl-sim/{strategy}/{dataset}")


def regions_of(pos: jax.Array, cfg, n_regions: int = 10) -> jax.Array:
    """(C,) int32 home road region per CAV (geographic non-iid ownership).

    Class ownership follows the home road region — scenes/scenarios are
    spatially correlated in C-ITS (DESIGN.md §9).
    """
    return jnp.floor(
        pos / cfg.ring_length_m * n_regions
    ).astype(jnp.int32) % n_regions


def twin_init_key(key: jax.Array) -> jax.Array:
    """THE fold chain from an experiment key to its twin-init key.

    Single source shared by ``init_state_traced`` and the engine's
    device-side data materialization (``derive_regions``): the regions a
    data row is partitioned by must come from the same twin spawn the
    experiment actually runs.
    """
    return fold_in_str(fold_in_str(key, "traffic-twin"), "init")


def derive_regions(key: jax.Array, scn) -> jax.Array:
    """(C,) home regions straight from the experiment key (traced)."""
    return regions_of(init_twin_state(scn, twin_init_key(key)).pos, scn)


def init_state_traced(
    init_params, fl: FLConfig, scn, key: jax.Array
) -> Tuple[RoundState, jax.Array]:
    """Build one experiment's initial ``RoundState`` plus its (C,) regions.

    Pure and traceable: ``init_params`` is a ``key -> params pytree``
    function (plain arrays, e.g. ``split_params(api.init(k))[0]``), ``scn``
    a concrete ``TrafficConfig`` or traced ``ScenarioParams``, ``key`` the
    pre-folded experiment key (``experiment_key``).  The batched engine
    vmaps this inside its compiled grid program so grid setup is pure key
    stacking; the host path (``init_state``) calls the SAME function
    eagerly — identical folds, bitwise-identical states.  The model pytree
    is flattened to the (P,) carry layout HERE — flatten/unflatten are
    exact (concat of fp32 ravels), so host and device init still agree
    bitwise leaf for leaf.

    Cheap (model params + twin kinematics only); the heavy client shards
    are a separate step (``make_round_data``) so the batched engine can
    defer them to the device inside its compiled grid program.
    """
    params = init_params(fold_in_str(key, "model-init"))
    params_vec, _ = flatten_to_vector(params)
    sketch_sign = sketch_sign_vector(
        fold_in_str(key, "selector"), params_vec.shape[0], fl.sketch_dim
    )
    twin_state = init_twin_state(scn, twin_init_key(key))
    regions = regions_of(twin_state.pos, scn)
    N = fl.num_clients
    # moments ALWAYS fp32 (derived from the fp32 init vector, before any
    # master downcast); params carry the master dtype, the fedbuff ring
    # the compute dtype — static gates, so the fp32 default traces the
    # exact historical program (zero casts)
    pd, cd = precision_of(fl)
    opt_m, opt_v = init_opt_vectors(params_vec)
    if pd != jnp.float32:
        params_vec = params_vec.astype(pd)
    state = RoundState(
        params=params_vec,
        opt_m=opt_m,
        opt_v=opt_v,
        twin=twin_state,
        sketches=jnp.zeros((N, fl.sketch_dim), jnp.float32),
        sketch_age=jnp.full((N,), jnp.inf, jnp.float32),
        clusters=jnp.zeros((N,), jnp.int32),
        sketch_sign=sketch_sign,
        buf_delta=jnp.zeros((fl.buffer_size, params_vec.shape[0]), cd),
        buf_arrive=jnp.zeros((fl.buffer_size,), jnp.float32),
        buf_sent=jnp.zeros((fl.buffer_size,), jnp.float32),
        buf_weight=jnp.zeros((fl.buffer_size,), jnp.float32),
        buf_mask=jnp.zeros((fl.buffer_size,), bool),
        round=jnp.zeros((), jnp.int32),
        sim_time=jnp.zeros((), jnp.float32),
        key=key,
    )
    return state, regions


def init_state(
    api,
    fl: FLConfig,
    traffic_cfg: TrafficConfig,
    dataset: str,
    strategy: str,
    key: jax.Array,
) -> Tuple[RoundState, jax.Array]:
    """Host-side build of one experiment's initial state (legacy loop path).

    Thin wrapper over ``init_state_traced`` with the strategy/dataset fold
    applied, run under jit — the device-resident engine path vmaps the same
    traced core, and jitted-single vs jitted-vmapped round identically
    (eager would round `mean + std * eps` without the FMA contraction), so
    the two inits are bitwise-identical (tests/test_engine.py parity).
    """
    assert fl.num_clients == traffic_cfg.num_vehicles, (
        "every FL client is a CAV: num_clients must equal num_vehicles"
    )
    key = fold_in_str(key, f"fl-sim/{strategy}/{dataset}")
    return _jitted_init(api, fl, traffic_cfg)(key)


@functools.lru_cache(maxsize=64)
def _jitted_init(api, fl: FLConfig, traffic_cfg: TrafficConfig):
    """One compiled host init per (api, fl, traffic) — repeated host-path
    inits (legacy grids, parity sweeps) reuse it instead of paying a fresh
    trace per call.  All three cache keys are hashable: the configs are
    frozen dataclasses, the api a NamedTuple of functions (identity-keyed,
    like jit's own function cache)."""
    return jax.jit(
        lambda k: init_state_traced(
            lambda kk: split_params(api.init(kk))[0], fl, traffic_cfg, k
        )
    )


def make_round_data(
    key: jax.Array, dataset: str, fl: FLConfig, regions: jax.Array
) -> RoundData:
    """Client shards + test set from (key, regions) — pure jnp.

    ``key`` is the experiment key (``RoundState.key``).  Runs eagerly on
    the host (legacy loop) or traced inside the engine's grid program
    (device-side partitioning): both paths produce identical arrays.
    """
    images, labels = partition_clients(key, dataset, fl, regions)
    test_x, test_y = make_test_set(key, dataset)
    return RoundData(images, labels, client_sample_counts(labels), test_x, test_y)


def init_experiment(
    api,
    fl: FLConfig,
    traffic_cfg: TrafficConfig,
    dataset: str,
    strategy: str,
    key: jax.Array,
) -> Tuple[RoundState, RoundData]:
    """Build the initial state + data shard for one experiment (host-side)."""
    state, regions = init_state(api, fl, traffic_cfg, dataset, strategy, key)
    return state, make_round_data(state.key, dataset, fl, regions)


def _row(leaf, data_idx):
    """A RoundData leaf for THIS experiment: lazy row gather when stacked."""
    return leaf if data_idx is None else leaf[data_idx]


def make_warmup(loss_fn, fl: FLConfig, param_spec):
    """Deadline-rule bootstrap: every client reports one gradient sketch,
    then the first clustering runs.  Pure: (state, data[, data_idx]) -> state."""
    _, cd = precision_of(fl)
    one_step = make_local_trainer(
        loss_fn, fl.learning_rate, 1, fl.batch_size,
        compute_dtype=None if cd == jnp.float32 else cd,
    )

    def warmup(state: RoundState, data: RoundData, data_idx=None) -> RoundState:
        bs = fl.batch_size
        params = unflatten_from_vector(state.params, param_spec)
        # each client trains on its first batch: the trainer's step gathers
        # its rows from this slice of the store, which every lane shares, so
        # no lane copies a client's shard
        axis = 1 if data_idx is None else 2
        images = jax.lax.slice_in_dim(data.images, 0, bs, axis=axis)
        labels = jax.lax.slice_in_dim(data.labels, 0, bs, axis=axis)
        lead = () if data_idx is None else (data_idx,)
        N = labels.shape[axis - 1]
        keys = jax.random.split(fold_in_str(state.key, "warmup"), N)

        def sketch_one(x):
            c, k = x
            vec = one_step(
                params, images, labels, k[None], rows=(*lead, c[None])
            )[1][0]
            return apply_sketch(vec, state.sketch_sign, fl.sketch_dim)

        # all N clients train and sketch in chunks of the cohort width, so
        # the bootstrap's peak memory is a round's: neither N clients'
        # activations nor their (N, P) update vectors exist at once
        sketches = jax.lax.map(
            sketch_one, (jnp.arange(N), keys), batch_size=fl.n_select
        )
        k_km = fold_in_str(jax.random.fold_in(state.key, 0), "kmeans")
        clusters, _ = kmeans_cluster(sketches, k_km, fl.num_clusters)
        return state._replace(
            sketches=sketches,
            sketch_age=jnp.zeros_like(state.sketch_age),
            clusters=clusters,
        )

    return warmup


def make_round_step(loss_fn, fl: FLConfig, cohort_size: int, model_bytes: float,
                    param_spec, strategies: Sequence[str] = STRATEGY_ORDER,
                    fused: bool = True,
                    aggregators: Sequence[str] = ("fedavg",)):
    """Build the pure round transition for a fixed FL config.

    Static arguments select the compiled program; ``scn`` (ScenarioParams or
    TrafficConfig), ``strategy_idx``, ``aggregator_idx``, ``do_eval`` and
    the optional ``do_recluster`` / ``data_idx`` are traced so the same
    program serves the whole grid.  ``strategy_idx`` indexes ``strategies``
    (not the global order): a vmapped switch executes every branch for
    every lane, so carrying only the grid's strategies matters.
    ``aggregator_idx`` indexes ``aggregators`` the same way (the registry
    in ``fl.aggregators``); the special single-rule ``("fedavg",)``
    registry — the default — traces the pre-registry reduce+AXPY path
    verbatim, keeping it bitwise-frozen.

    ``fused`` selects the one-sweep ``rttg_latency`` geometry path
    (default) vs the legacy composition — bitwise-identical by contract.

    Two-tier aggregation (``fl.hierarchical``): FedAvg weights route
    through per-RSU sample-count masses (clients reduce into their
    attached RSU, live RSUs reduce into the server; dark RSUs drop their
    partial) — bitwise-identical to the flat lane while every RSU is live,
    because the masses are integer-valued (tests/test_hierarchical.py).
    ``fl.client_block > 0`` additionally STREAMS the cohort: an inner
    ``lax.scan`` trains fixed-size client chunks and segment-reduces each
    into (R, P) per-RSU partials riding the chunk carry
    (``kernels.ops.rsu_reduce_auto``), so the full (K, P) update matrix
    never materializes and the server step reduces R partials through the
    same fused ``server_update`` pass — the ``num_clients`` scaling path.
    Round ECONOMICS (selection, duration, twin, metrics) are computed
    before training from the same expressions in both modes, so they stay
    bitwise across flat/hierarchical/blocked lanes; the blocked lane's
    model update reassociates the cohort sum per RSU (allclose, exact for
    the all-live integer-weight case chunk-wise).
    """
    strategies = tuple(strategies)
    hierarchical = bool(getattr(fl, "hierarchical", False))
    client_block = int(getattr(fl, "client_block", 0))
    if client_block < 0:
        raise ValueError(f"client_block must be >= 0, got {client_block}")
    if client_block and not hierarchical:
        raise ValueError(
            "client_block streaming segments the cohort by RSU attachment; "
            "set hierarchical=True to enable it"
        )
    aggregators = validate_aggregators(aggregators)
    # local aggregator index -> global AGGREGATOR_ORDER index (the fused
    # server_update pass and the STALE_IDX test both speak global)
    agg_global = jnp.asarray(
        [AGGREGATOR_ORDER.index(a) for a in aggregators], jnp.int32
    )
    plain_fedavg = aggregators == ("fedavg",)
    # fedbuff lanes carry the in-flight delta ring buffer (RoundState.buf_*)
    # through the server step; registries without it keep the unbuffered
    # kernel (and the buffer leaves ride the carry as inert zeros)
    has_fedbuff = "fedbuff" in aggregators
    Kb = int(fl.buffer_size)
    buffer_fill = int(fl.buffer_fill)
    # the buffered kernel's working set adds the (Kb, block_p) buffer tile
    # to the cohort tile — budget the extra rows so the VMEM invariant holds
    buf_rows = Kb if has_fedbuff else 0
    hp = server_hp(fl)
    # precision axis (FLConfig.param_dtype / compute_dtype): every gate
    # below is STATIC — the fp32/fp32 default contains zero casts and
    # traces the exact pre-axis program (tests/test_precision.py holds the
    # bitwise contract; the bf16 lane halves the comm payload, the update
    # rows, the fedbuff ring and the chunk partials while the fp32 master
    # + moments and every kernel's fp32 accumulation absorb the rounding)
    _, cd = precision_of(fl)
    half = cd != jnp.float32
    itemsize = cd.itemsize
    trainer = make_local_trainer(
        loss_fn, fl.learning_rate, fl.local_epochs, fl.batch_size,
        mu=fl.fedprox_mu, compute_dtype=cd if half else None,
    )
    n_select = fl.n_select
    N, K = fl.num_clients, cohort_size
    P = flat_size_of(param_spec)
    compute_s = fl.local_epochs * fl.compute_s_per_epoch
    # the latency economics price the bytes a vehicle actually uploads:
    # half-width deltas halve the payload (exact *1.0 for the fp32 lane,
    # so the default round physics stay bitwise)
    mb = jnp.asarray(model_bytes * (itemsize / 4.0), jnp.float32)
    cr = fl.connection_rate
    nan = jnp.float32(jnp.nan)

    def _eval(params_vec, data, data_idx):
        params = unflatten_from_vector(params_vec, param_spec)
        batch = {"images": _row(data.test_x, data_idx),
                 "labels": _row(data.test_y, data_idx)}
        m = loss_fn(params, batch)[1]
        return m["accuracy"].astype(jnp.float32), m["ce"].astype(jnp.float32)

    def _forced(key):
        """The forced connection-rate Bernoulli (Tab. I's CR < 1 rows).

        Drawn OUTSIDE the fused kernel — identical key, identical shape to
        the draw ``core.network.connectivity`` makes inside the unfused
        composition, so the two paths consume the same bits.
        """
        if cr >= 1.0:
            return None
        return jax.random.bernoulli(key, cr, (N,))

    def _predicted(twin, scn, rk):
        """Stage 1+2 geometry: fused observations -> predicted latency/conn."""
        k_obs = fold_in_str(rk, "observe")
        cams = emit_cams(twin, scn, k_obs)
        cpms = emit_cpms(twin, scn, k_obs)
        k_cr = fold_in_str(rk, "cr")
        if fused:
            # one-sweep path: plain fused kinematics straight into the
            # rttg_latency chain — no intermediate RTTG, no (N, N) adjacency
            pos, speed, accel, _ = fuse_kinematics(cams, cpms, scn)
            return rttg_latency_auto(
                pos, speed, accel, twin.t, mb, _forced(k_cr), scn, predict=True
            )
        rttg = fuse_messages(cams, cpms, twin.t, scn)
        future = predict_rttg(rttg, scn.predict_horizon_s, scn)
        lat_pred = latency_model(future, mb, scn)
        connected = connectivity(future, scn, cr, k_cr)
        return lat_pred, connected

    def _realized(mid_twin, scn, rk):
        """Mid-round geometry on the TRUE evolved topology.

        The hierarchical lanes additionally need the attachment ids the
        chain's argmin already resolved (segmenting the edge reduce), so
        they arrive as a third output — adding it leaves the latency /
        connectivity expressions untouched in both compositions.
        """
        k_cr = fold_in_str(rk, "upload-cr")
        if fused:
            return rttg_latency_auto(
                mid_twin.pos, mid_twin.speed, mid_twin.accel, mid_twin.t, mb,
                _forced(k_cr), scn, predict=False, want_rid=hierarchical,
            )
        mid_rttg = build_rttg(
            mid_twin.t, mid_twin.pos, mid_twin.speed, mid_twin.accel,
            jnp.zeros_like(mid_twin.pos), scn,
        )
        real_lat = latency_model(mid_rttg, mb, scn)
        still_conn = connectivity(mid_rttg, scn, cr, k_cr)
        if hierarchical:
            return real_lat, still_conn, mid_rttg.rsu_id.astype(jnp.int32)
        return real_lat, still_conn

    def _elect(connected, lat_pred, clusters, k, strategy_idx):
        """Stage 4: election over the predicted topology via lax.switch."""
        branches = [
            functools.partial(
                lambda name, kk, conn, lat, cl: STRATEGIES[name](
                    fold_in_str(kk, name), conn, lat, cl, n_select, fl.gamma
                ),
                name,
            )
            for name in strategies
        ]
        if len(branches) == 1:
            return branches[0](k, connected, lat_pred, clusters)
        return jax.lax.switch(
            strategy_idx, branches, k, connected, lat_pred, clusters
        )

    def round_step(state: RoundState, scn, strategy_idx, aggregator_idx,
                   data: RoundData, do_eval, do_recluster=None, data_idx=None):
        # each stage's ops carry an ``fl.<stage>`` scope in their op_name
        # (repro.utils.tracing), which names their device time in a trace
        with stage("geometry"):
            # ---- stages 1+2: fuse CAM/CPM, predict, price the topology -
            rk = jax.random.fold_in(state.key, state.round)
            lat_pred, connected = _predicted(state.twin, scn, rk)

        with stage("select"):
            # ---- stage 4: elect --------------------------------------------
            mask = _elect(connected, lat_pred, state.clusters, rk, strategy_idx)
            n_selected = jnp.sum(mask).astype(jnp.int32)

        with stage("train"):
            # ---- fixed-size cohort slots -----------------------------------
            # Selected client ids in ascending order fill the first slots; the
            # rest are no-op padding (zeroed data + zeroed updates) — never a
            # redundant retraining of client 0.  The trainer gathers each SGD
            # batch straight from the store by (data_idx, idx_c[k], sample):
            # neither a per-lane copy of the client shards nor the cohort's
            # (K, n, D) block is ever made.
            order = jnp.where(mask, jnp.arange(N), N + jnp.arange(N))
            idx = jnp.sort(order)[:K]
            slot_valid = idx < N
            idx_c = jnp.where(slot_valid, idx, 0)

        with stage("geometry"):
            # ---- realized round economics on the TRUE evolved topology -----
            # Computed BEFORE training: a pure dataflow reorder (every PRNG
            # stream is name-folded and nothing here reads the updates), so
            # flat lanes trace the same values bitwise — and the blocked lane
            # must know the per-client weights before its chunk scan trains
            # anything.
            compute_i = compute_s * state.twin.compute_factor[idx_c]
            nsel_f = jnp.maximum(n_selected.astype(jnp.float32), 1.0)
            mean_compute = jnp.sum(jnp.where(slot_valid, compute_i, 0.0)) / nsel_f
            mid_twin = advance_twin(
                state.twin, scn, fold_in_str(rk, "mid"), mean_compute,
                num_substeps=ADVANCE_SUBSTEPS,
            )
            if hierarchical:
                real_lat, still_conn, rid = _realized(mid_twin, scn, rk)
            else:
                real_lat, still_conn = _realized(mid_twin, scn, rk)
            ok = slot_valid & still_conn[idx_c]
            ok_any = jnp.any(ok)
            timeout = jnp.float32(fl.round_timeout_s)
            per_slot = real_lat[idx_c] + compute_i
            # a selected client that missed the deadline costs the full timeout;
            # padding slots must not contribute to the round maximum
            slot_pay = jnp.where(ok, per_slot, timeout)
            dur_core = jnp.max(jnp.where(slot_valid, slot_pay, -jnp.inf))
            duration = jnp.where(
                n_selected > 0, dur_core + fl.server_agg_s, timeout
            )

        with stage("server"):
            # ---- FedAvg weights (flat, or RSU-routed two-tier) -------------
            # weights come from the per-client sample counts the data row
            # carries (equal to fl.samples_per_client while every slot fills)
            counts_k = _row(data.counts, data_idx)[idx_c]
            if hierarchical:
                R = n_rsu_of(scn)
                live = rsu_up_mask(scn)
                rid_k = rid[idx_c]
                # the attachment argmin never picks a dark RSU, so this fold is
                # the identity whenever attachments are current — it is the
                # contract that a dark RSU's partial NEVER reaches the server
                live_k = live[rid_k]

                def _w_strict(m, c):
                    return rsu_normalized_weights(m & live_k, c, rid_k, live, R)[0]

                def _w_stale(m, c):
                    # float-valued discounted counts don't reassociate exactly:
                    # keep the flat-sum normalizer (mass_norm=False) so the
                    # stale lane stays bitwise with its flat sibling too
                    return rsu_normalized_weights(
                        m & live_k, c, rid_k, live, R, mass_norm=False
                    )[0]
            else:
                _w_strict = _w_stale = normalized_weights

            if plain_fedavg:
                # THE pre-registry path: plain FedAvg weights, server moment
                # vectors ride the carry untouched
                w = _w_strict(ok, counts_k)
                upd_any = ok_any
            else:
                gidx = agg_global[aggregator_idx]
                is_stale = gidx == STALE_IDX
                # stale rule: deadline-missing stragglers keep a discounted
                # weight from their REALIZED round time instead of dropping to
                # zero; survivors and every other rule keep the strict weights
                # bitwise (jnp.where passes the untaken side through untouched)
                w_strict = _w_strict(ok, counts_k)
                disc = jnp.where(ok, 1.0, staleness_scale(per_slot, timeout))
                w_stale = _w_stale(slot_valid, counts_k * disc)
                w = jnp.where(is_stale, w_stale, w_strict)
                # under stale ANY selected client contributes an update; round
                # economics (duration, base twin, metrics) keep the strict
                # deadline semantics so aggregator lanes stay comparable (see
                # the module docstring for how far that identity extends)
                upd_any = jnp.where(is_stale, n_selected > 0, ok_any)

            # ---- fedbuff: drain arrived buffer slots, place new stragglers -
            # All mask-based on the fixed (Kb,) slot axis: which occupied slots
            # have ARRIVED by round end drains into the server step (discounted
            # by realized cross-round lateness, gated on the fill threshold);
            # this round's deadline-missers compact into the freed slots.
            if has_fedbuff:
                is_fedbuff = gidx == FEDBUFF_IDX
                end_time = state.sim_time + duration
                arrived = state.buf_mask & (state.buf_arrive <= end_time)
                n_arrived = jnp.sum(arrived).astype(jnp.int32)
                drain_fire = is_fedbuff & (n_arrived >= buffer_fill)
                disc_b = staleness_scale(
                    jnp.maximum(end_time - state.buf_sent, 0.0), timeout
                )
                # normalize by the UNDISCOUNTED drained mass (the same 1e-9
                # guard as normalized_weights) so the staleness discount
                # genuinely shrinks the step instead of cancelling out
                mass_b = jnp.sum(jnp.where(arrived, state.buf_weight, 0.0))
                bw = jnp.where(
                    drain_fire & arrived,
                    state.buf_weight * disc_b / jnp.maximum(mass_b, 1e-9),
                    0.0,
                )
                keep = state.buf_mask & ~(drain_fire & arrived)
                # free-slot compaction: the i-th straggler takes the i-th free
                # slot; ranks beyond the free capacity gather values >= Kb and
                # the scatters below drop them (newest-overflow-dropped policy)
                strag = slot_valid & ~ok & is_fedbuff
                free_order = jnp.sort(
                    jnp.where(keep, Kb + jnp.arange(Kb), jnp.arange(Kb))
                )
                rank = jnp.cumsum(strag) - 1
                slot = jnp.where(
                    strag & (rank < Kb),
                    free_order[jnp.clip(rank, 0, Kb - 1)],
                    2 * Kb,
                )
                n_buffered = jnp.sum(strag & (slot < Kb)).astype(jnp.int32)
                n_drained = jnp.where(drain_fire, n_arrived, 0).astype(jnp.int32)
                # a drain with zero in-round survivors is still a server step
                upd_any = jnp.where(is_fedbuff, ok_any | drain_fire, upd_any)
            else:
                n_buffered = jnp.zeros((), jnp.int32)
                n_drained = jnp.zeros((), jnp.int32)

        with stage("train"):
            # ---- local training + edge reduce ------------------------------
            params = unflatten_from_vector(state.params, param_spec)
            lead = () if data_idx is None else (data_idx,)
            if client_block:
                # chunk-streamed two-tier lane: an inner scan trains fixed-size
                # client chunks and segment-reduces each straight into (R, P)
                # per-RSU partials riding the chunk carry — the full (K, P)
                # update matrix never materializes.  Per-client PRNG keys come
                # from ONE cohort-wide split (the exact stream the unblocked
                # trainer consumes), sliced per chunk; padding slots repeat
                # key 0 and train zeroed data into zero-masked updates.
                B = client_block
                nC = -(-K // B)
                pad = nC * B - K

                def _pad_k(x, fill):
                    if pad == 0:
                        return x
                    return jnp.concatenate(
                        [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)]
                    )

                keys_all = jax.random.split(fold_in_str(rk, "local"), K)
                if pad:
                    kd = jax.random.key_data(keys_all)
                    kd = jnp.concatenate([kd, jnp.tile(kd[:1], (pad, 1))])
                    keys_all = jax.random.wrap_key_data(kd)
                xs = (
                    _pad_k(idx_c, 0).reshape(nC, B),
                    _pad_k(slot_valid, False).reshape(nC, B),
                    _pad_k(w, 0.0).reshape(nC, B),
                    _pad_k(rid_k, 0).reshape(nC, B),
                    _pad_k(ok, False).reshape(nC, B),
                    keys_all.reshape(nC, B),
                )
                if has_fedbuff:
                    # ring-buffer slot per cohort position (>= Kb drops);
                    # padding chunks scatter nowhere
                    xs = xs + (_pad_k(slot, 2 * Kb).reshape(nC, B),)

                def _chunk(carry, xs_c):
                    if has_fedbuff:
                        partials, sketches, sketch_age, buf = carry
                        i_c, v_c, w_c, r_c, ok_c, k_c, s_c = xs_c
                    else:
                        partials, sketches, sketch_age = carry
                        i_c, v_c, w_c, r_c, ok_c, k_c = xs_c
                    _, vb = trainer(params, data.images, data.labels, k_c,
                                    rows=(*lead, i_c), valid=v_c)
                    vb = vb * v_c[:, None]
                    if half:
                        # the comm lane: chunk deltas travel (and park in the
                        # fedbuff ring) at the compute dtype
                        vb = vb.astype(cd)
                    part_c, _ = rsu_reduce_auto(
                        vb, w_c, r_c, R, out_dtype=cd if half else None
                    )
                    sks_c = jax.vmap(
                        lambda v: apply_sketch(v, state.sketch_sign, fl.sketch_dim)
                    )(vb)
                    scat = jnp.where(ok_c, i_c, N)  # out-of-bounds rows drop
                    sketches = sketches.at[scat].set(sks_c, mode="drop")
                    sketch_age = sketch_age.at[scat].set(0.0, mode="drop")
                    if has_fedbuff:
                        # straggler updates park in the ring buffer (vb is
                        # already zero-masked on padding slots)
                        buf = buf.at[s_c].set(vb, mode="drop")
                        return (partials + part_c, sketches, sketch_age, buf), None
                    return (partials + part_c, sketches, sketch_age), None

                # the (R, P) per-RSU partials ride the chunk carry at the
                # compute dtype (fp32 default; bf16 halves the carry)
                carry0 = (jnp.zeros((R, P), cd), state.sketches,
                          state.sketch_age)
                if has_fedbuff:
                    carry0 = carry0 + (
                        jnp.where(keep[:, None], state.buf_delta, 0.0),
                    )
                    (partials, sketches, sketch_age, buf_delta), _ = jax.lax.scan(
                        _chunk, carry0, xs
                    )
                else:
                    (partials, sketches, sketch_age), _ = jax.lax.scan(
                        _chunk, carry0, xs
                    )
                sketch_age = sketch_age + 1.0
                # server tier: R live partials (weights already folded in at
                # the edge) reduce through the same fused flat pass
                red, red_w, bp = partials, live.astype(jnp.float32), \
                    pick_block_p(R + buf_rows, P, itemsize=itemsize)
            else:
                _, vecs = trainer(params, data.images, data.labels,
                                  fold_in_str(rk, "local"),
                                  rows=(*lead, idx_c), valid=slot_valid)
                vecs = vecs * slot_valid[:, None]
                if half:
                    # the comm lane: update vectors travel to the reduce (and
                    # park in the fedbuff ring) at the compute dtype
                    vecs = vecs.astype(cd)

                # ---- deadline rule: survivors report sketches --------------
                sks = jax.vmap(
                    lambda v: apply_sketch(v, state.sketch_sign, fl.sketch_dim)
                )(vecs)
                scatter = jnp.where(ok, idx_c, N)  # out-of-bounds rows drop
                sketches = state.sketches.at[scatter].set(sks, mode="drop")
                sketch_age = state.sketch_age.at[scatter].set(0.0, mode="drop") + 1.0
                if has_fedbuff:
                    # straggler updates park in the ring buffer: drained slots
                    # zero out, this round's deadline-missers scatter into the
                    # freed slots (slot >= Kb rows drop)
                    buf_delta = jnp.where(
                        keep[:, None], state.buf_delta, 0.0
                    ).at[slot].set(vecs, mode="drop")
                red, red_w, bp = vecs, w, pick_block_p(K + buf_rows, P,
                                                       itemsize=itemsize)

        with stage("server"):
            # ---- server update over deadline survivors (one fused flat pass)
            if plain_fedavg:
                delta = fedavg_reduce_auto(red, red_w, block_p=bp)
                params_vec = jnp.where(
                    upd_any, apply_delta_flat(state.params, delta), state.params
                )
                opt_m, opt_v = state.opt_m, state.opt_v
            elif has_fedbuff:
                # every lane of a fedbuff-bearing registry routes through the
                # buffered kernel: drain=False passes the unbuffered delta
                # through bitwise, so non-fedbuff lanes are unchanged.  The
                # PRE-scatter buffer is reduced — bw is nonzero only on slots
                # drained this round.
                new_p, new_m, new_v = server_update_buffered_auto(
                    red, red_w, state.buf_delta, bw, state.params, state.opt_m,
                    state.opt_v, gidx, state.round, drain_fire, eta=hp.eta,
                    beta1=hp.beta1, beta2=hp.beta2, tau=hp.tau, block_p=bp,
                )
                params_vec = jnp.where(upd_any, new_p, state.params)
                opt_m = jnp.where(upd_any, new_m, state.opt_m)
                opt_v = jnp.where(upd_any, new_v, state.opt_v)
            else:
                new_p, new_m, new_v = server_update_auto(
                    red, red_w, state.params, state.opt_m, state.opt_v, gidx,
                    state.round, eta=hp.eta, beta1=hp.beta1, beta2=hp.beta2,
                    tau=hp.tau, block_p=bp,
                )
                params_vec = jnp.where(upd_any, new_p, state.params)
                opt_m = jnp.where(upd_any, new_m, state.opt_m)
                opt_v = jnp.where(upd_any, new_v, state.opt_v)

            # ---- fedbuff: ring-buffer metadata follows the delta scatter ---
            if has_fedbuff:
                # a parked straggler's update is modeled as landing one full
                # deadline later (or its realized round time, if even slower)
                arrive_k = state.sim_time + jnp.maximum(per_slot, timeout)
                buf_arrive = jnp.where(
                    keep, state.buf_arrive, 0.0
                ).at[slot].set(arrive_k, mode="drop")
                buf_sent = jnp.where(
                    keep, state.buf_sent, 0.0
                ).at[slot].set(jnp.broadcast_to(state.sim_time, (K,)), mode="drop")
                buf_weight = jnp.where(
                    keep, state.buf_weight, 0.0
                ).at[slot].set(counts_k, mode="drop")
                buf_mask = keep.at[slot].set(jnp.ones((K,), bool), mode="drop")
            else:
                buf_delta = state.buf_delta
                buf_arrive = state.buf_arrive
                buf_sent = state.buf_sent
                buf_weight = state.buf_weight
                buf_mask = state.buf_mask

        with stage("geometry"):
            # ---- advance the twin to round end -----------------------------
            base = jax.tree_util.tree_map(
                lambda m, o: jnp.where(ok_any, m, o), mid_twin, state.twin
            )
            already = jnp.where(ok_any, mean_compute, 0.0)
            rem = jnp.maximum(duration - already, 1e-3)
            twin = advance_twin(
                base, scn, fold_in_str(rk, "adv"), rem, num_substeps=ADVANCE_SUBSTEPS
            )

        with stage("select"):
            # ---- end of round: recluster on schedule ----------------------
            # ``do_recluster`` arrives UNBATCHED from the engine's scan xs so
            # the cond stays a genuine branch under vmap (a batched predicate
            # would lower to a select that runs k-means EVERY round for every
            # lane); the legacy host loop derives it from the (unbatched)
            # round counter instead — same value, same branch.
            new_round = state.round + 1
            if do_recluster is None:
                do_recluster = new_round % max(fl.recluster_every, 1) == 0
            k_km = fold_in_str(jax.random.fold_in(state.key, new_round), "kmeans")
            clusters = jax.lax.cond(
                do_recluster,
                lambda: kmeans_cluster(sketches, k_km, fl.num_clusters)[0],
                lambda: state.clusters,
            )
        sim_time = state.sim_time + duration
        with stage("eval"):
            # ---- strided eval (the same unbatched-predicate cond) --------
            test_acc, test_loss = jax.lax.cond(
                do_eval,
                lambda p: _eval(p, data, data_idx),
                lambda p: (nan, nan),
                params_vec,
            )

        metrics = RoundMetrics(
            round=new_round,
            sim_time=sim_time,
            duration=duration,
            n_selected=n_selected,
            n_succeeded=jnp.sum(ok).astype(jnp.int32),
            n_buffered=n_buffered,
            n_drained=n_drained,
            mean_pred_latency=jnp.where(
                n_selected > 0, jnp.sum(jnp.where(mask, lat_pred, 0.0)) / nsel_f, nan
            ),
            mean_real_latency=jnp.where(
                n_selected > 0,
                jnp.sum(jnp.where(slot_valid, real_lat[idx_c], 0.0)) / nsel_f,
                nan,
            ),
            test_acc=test_acc,
            test_loss=test_loss,
        )
        new_state = state._replace(
            params=params_vec,
            opt_m=opt_m,
            opt_v=opt_v,
            twin=twin,
            sketches=sketches,
            sketch_age=sketch_age,
            clusters=clusters,
            buf_delta=buf_delta,
            buf_arrive=buf_arrive,
            buf_sent=buf_sent,
            buf_weight=buf_weight,
            buf_mask=buf_mask,
            round=new_round,
            sim_time=sim_time,
        )
        return new_state, metrics

    return round_step


def metrics_to_records(metrics: RoundMetrics) -> list:
    """Convert stacked (T,) RoundMetrics into host RoundRecords."""
    import numpy as np

    m = jax.tree_util.tree_map(np.asarray, metrics)
    out = []
    for i in range(m.round.shape[0]):
        out.append(
            RoundRecord(
                round=int(m.round[i]),
                sim_time=float(m.sim_time[i]),
                duration=float(m.duration[i]),
                n_selected=int(m.n_selected[i]),
                n_succeeded=int(m.n_succeeded[i]),
                n_buffered=int(m.n_buffered[i]),
                n_drained=int(m.n_drained[i]),
                mean_pred_latency=float(m.mean_pred_latency[i]),
                mean_real_latency=float(m.mean_real_latency[i]),
                test_acc=float(m.test_acc[i]),
                test_loss=float(m.test_loss[i]),
            )
        )
    return out
