"""Smoke run of the FL experiment engine on a TPU chip.

    python chip_smoke.py               # paper and fleet phases, one chip
    python chip_smoke.py --four-chips  # sharded vs vmapped grid, four chips

Each phase builds an ``ExperimentEngine`` and calls ``run_grid`` twice.  The
first call compiles the grid program ahead of time, so the compile is timed
apart and its text can be searched for the Pallas kernels
(``tpu_custom_call``); the second call runs the same program again.  A phase
fails unless every metric is finite, its compiled program holds the kernels
it routes through, and each of those kernels, run on the chip at the phase's
shapes, matches its plain ``kernels.ref`` form: the reductions' refs run on
the host CPU, ``rttg_latency``'s on the chip (see ``RTTG_RTOL``).

The seconds printed are smoke timings, not benchmark metrics.  The last line
of stdout is a JSON object naming the device, printed only when every check
passed.  Any failure, or a backend other than a TPU, exits nonzero.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import FLConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.network import snr_from_dist  # noqa: E402
from repro.core.rttg import n_rsu_of, rsu_up_mask  # noqa: E402
from repro.core.scenarios import scenario_config, scenario_params  # noqa: E402
from repro.core.trajectory import horizon_steps, predict_kinematics  # noqa: E402
from repro.fl.aggregators import AGGREGATOR_ORDER, server_hp  # noqa: E402
from repro.fl.engine import ExperimentEngine  # noqa: E402
from repro.fl.rounds import flat_size_of  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_grid_mesh  # noqa: E402
from repro.utils import tree_bytes  # noqa: E402

from bench import trace  # noqa: E402

EPS32 = float(np.finfo(np.float32).eps)


@dataclasses.dataclass(frozen=True)
class Phase:
    """One grid sweep through ``ExperimentEngine.run_grid``."""

    name: str
    model: str
    dataset: str
    fl: FLConfig
    strategies: tuple
    aggregators: tuple
    scenarios: tuple
    rounds: int
    warmup: bool
    # the jit wrappers whose pallas_call the compiled grid holds: any
    # wrapper on the op path of a kernel bench.trace.kernel_instructions
    # finds (server_update_buffered's pallas_call sits in server_update's)
    kernels: tuple

    def engine(self, mesh=None) -> ExperimentEngine:
        return ExperimentEngine(
            get_config(self.model), self.fl, self.dataset,
            strategies=self.strategies, aggregators=self.aggregators,
            warmup=self.warmup, mesh=mesh,
        )

    def run_kwargs(self) -> dict:
        return dict(seeds=(0,), scenarios=self.scenarios, rounds=self.rounds,
                    eval_every=self.rounds)

    @property
    def lanes(self) -> int:
        return len(self.strategies) * len(self.aggregators) * len(self.scenarios)


# The paper's section IV setting at full width: FLConfig() holds its
# defaults (100 vehicles, a 10% cohort, 512 samples per client, batch 64,
# one local epoch, sketch_dim 1024, 10 clusters).  fedbuff in the registry
# routes every lane through server_update_buffered.  The paper's four
# strategies make 24 lanes, whose grid program needs 17.28 GB of HBM when
# compiled for a v5e (15.75 GB usable), so one strategy is cut: 18 lanes.
PAPER_CUT = ("gossip",)
PAPER = Phase(
    name="paper", model="fl-cifar10-cnn", dataset="cifar10", fl=FLConfig(),
    strategies=("contextual", "data", "network"),
    aggregators=("fedavg", "fedadam", "fedbuff"),
    scenarios=("ring", "rush_hour"), rounds=3, warmup=True,
    kernels=("_rttg_latency", "server_update_buffered"),
)

# The fleet-20000 lane of benchmarks/engine_throughput.py (two-tier RSU
# aggregation over a chunk-streamed cohort of ~100), on the bf16 lane.
_FLEET_N = 20_000
FLEET = Phase(
    name="fleet", model="fl-mnist-mlp", dataset="mnist",
    fl=FLConfig(
        num_clients=_FLEET_N, samples_per_client=2, batch_size=2,
        num_clusters=8, sketch_dim=64, select_fraction=100.0 / _FLEET_N,
        hierarchical=True, client_block=32, param_dtype="float32",
        compute_dtype="bfloat16",
    ),
    strategies=("contextual",), aggregators=("fedavg",),
    scenarios=("rush_hour",), rounds=2, warmup=False,
    kernels=("_rttg_latency", "rsu_reduce", "fedavg_reduce"),
)


def kernel_shapes(phase: Phase) -> dict:
    """The shapes the phase's grid program hands its kernels, per lane."""
    eng = phase.engine()
    eng._ensure_spec()
    n = phase.fl.num_clients
    return dict(
        N=n, K=eng.cohort_size, Kb=phase.fl.buffer_size,
        P=flat_size_of(eng.param_spec),
        R=n_rsu_of(scenario_config(phase.scenarios[-1], num_vehicles=n)),
        block=phase.fl.client_block, G=phase.lanes,
        rows=jnp.dtype(phase.fl.compute_dtype),
    )


def say(phase: str, msg: str) -> None:
    print(f"smoke {phase}: {msg}", flush=True)


# ---- the grid program, compiled ahead of time -----------------------------

class AheadOfTime:
    """Stands in for the engine's jitted grid program.

    The first call lowers and compiles it ahead of time, so the compile is
    timed apart from the run and its text can be read; every call then runs
    that one executable.
    """

    def __init__(self, eng: ExperimentEngine, phase: str):
        self.eng, self.phase = eng, phase
        self.jitted = eng._grid_fn
        self.compiled = None
        self.lower_s = self.compile_s = None

    def __call__(self, *args, **static):
        if self.compiled is None:
            self._reckon(args)
            t0 = time.perf_counter()
            lowered = self.jitted.lower(*args, **static)
            t1 = time.perf_counter()
            # the persistent compile cache serves this step, not the lowering
            self.compiled = lowered.compile()
            self.lower_s, self.compile_s = t1 - t0, time.perf_counter() - t1
        return self.compiled(*args)

    def _reckon(self, args):
        """Resident bytes of the data rows and the carry, by ``eval_shape``."""
        states, datas, scns = args[:3]
        data_b = tree_bytes(jax.eval_shape(self.eng._materialize, datas))
        carry_b = tree_bytes(
            jax.eval_shape(self.eng._init_states, states, scns)
        )
        say(self.phase, f"resident bytes by eval_shape: data rows {data_b}, "
                        f"carry {carry_b}, total {data_b + carry_b}")


def check_metrics(phase: str, metrics, eval_every: int) -> None:
    """Every leaf finite in every lane, except test_acc / test_loss on the
    rounds that do not evaluate, which hold NaN by design."""
    rounds = np.asarray(metrics.round).shape[1]
    evals = np.array([(r + 1) % eval_every == 0 or r == rounds - 1
                      for r in range(rounds)])
    if not np.all(np.asarray(metrics.n_selected) > 0):
        raise AssertionError(f"{phase}: a round selected no client")
    for field in metrics._fields:
        x = np.asarray(getattr(metrics, field), np.float64)
        if field in ("test_acc", "test_loss"):
            ok = np.all(np.isfinite(x[:, evals])) and np.all(np.isnan(x[:, ~evals]))
        else:
            ok = np.all(np.isfinite(x))
        if not ok:
            raise AssertionError(f"{phase}: metric {field} is not finite: {x}")


def run_phase(phase: Phase) -> None:
    eng = phase.engine()
    aot = AheadOfTime(eng, phase.name)
    eng._grid_fn = aot
    t0 = time.perf_counter()
    res = eng.run_grid(**phase.run_kwargs())
    jax.block_until_ready(res.metrics)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res2 = eng.run_grid(**phase.run_kwargs())
    jax.block_until_ready(res2.metrics)
    steady_s = time.perf_counter() - t0
    say(phase.name, f"smoke timing, not a benchmark metric: {phase.lanes} lanes "
                    f"x {phase.rounds} rounds, lower {aot.lower_s:.3f} s, "
                    f"compile {aot.compile_s:.3f} s, "
                    f"first run_grid {first_s:.3f} s, steady run_grid "
                    f"{steady_s:.3f} s")
    mem = aot.compiled.memory_analysis()
    if mem is not None:
        say(phase.name, f"compiled program bytes: temp {mem.temp_size_in_bytes}"
                        f", output {mem.output_size_in_bytes}")
    for r in (res, res2):
        check_metrics(phase.name, r.metrics, phase.rounds)
    hlo = aot.compiled.as_text()
    kernels, paths = trace.kernel_instructions(hlo), trace.op_paths(hlo)
    names = collections.Counter(w for k in kernels
                                for w in set(re.findall(r"jit\((\w+)\)", paths[k])))
    say(phase.name, f"tpu_custom_call ops in the grid program: {len(kernels)} "
                    f"({', '.join(f'{k} {names[k]}' for k in phase.kernels)})")
    missing = [k for k in phase.kernels if not names[k]]
    if missing:
        raise AssertionError(f"{phase.name}: no Pallas kernel from {missing} "
                             "in the compiled grid program")
    acc = {"/".join(map(str, k)): round(v, 4)
           for k, v in res.final_accuracy().items()}
    say(phase.name, f"final test accuracy per lane: {acc}")


# ---- each kernel on the chip against its ref -----------------------------

def reduce_rtol(k: int) -> float:
    """Relative bound between two fp32 reductions of k positive products.

    Each side lands within (k+1)*eps of the exact sum (the recursive
    summation bound), so the two differ by at most 2(k+1)*eps; the server
    rules add a few eps and fedadam's sqrt and divide double the reduce
    error, so 8(k+2)*eps covers them.  A bf16 computation misses by ~2**-9,
    about 100x more at the cohorts run here.
    """
    return 8 * (k + 2) * EPS32


# rttg_latency's ref runs on the chip as well: the chip's log10, pow and
# log2 differ from the host's by up to ~3e-4 relative (measured on a v5e),
# so a host ref would measure the math library and not the kernel.  On one
# chip, Mosaic's lowering of the elementwise fp32 chain and XLA's agree to
# a few ulp, which 1e-5 (84 ulp) covers.  A bf16 chain rounds positions to
# tens of metres at 8 km.
RTTG_RTOL = 1e-5
_ref_rttg = jax.jit(ref.rttg_latency, static_argnums=7,
                    static_argnames="want_rid")


def compare(phase: str, name: str, got, want, rtol: float) -> None:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    tiny = np.finfo(np.float32).tiny  # an RSU with no member sums to 0
    err = float(np.max(np.abs(got - want) / np.maximum(np.abs(want), tiny)))
    say(phase, f"kernel {name}: max relative error vs ref {err:.3e} "
               f"(bound {rtol:.3e})")
    if not err <= rtol:
        raise AssertionError(f"{phase}: kernel {name} is off its ref by "
                             f"{err:.3e} > {rtol:.3e}")


def compare_exact(phase: str, name: str, got, want) -> None:
    bad = int(np.sum(np.asarray(got) != np.asarray(want)))
    say(phase, f"kernel {name}: {bad} mismatches vs ref")
    if bad:
        raise AssertionError(f"{phase}: kernel {name} differs from its ref "
                             f"in {bad} entries")


def clear_of_ties(pos, speed, accel, cfg, predict: bool, cpu):
    """Shift starting positions until no (predicted) vehicle lies within
    half a metre of a tie between two RSUs or of the SNR threshold, where
    one ulp decides the attachment or the link."""
    L = float(cfg.ring_length_m)
    rsu = np.arange(int(cfg.n_rsu)) * float(cfg.rsu_spacing_m)
    live = np.asarray(rsu_up_mask(cfg))
    cfg = jax.device_put(cfg, cpu)
    for _ in range(8):
        with jax.default_device(cpu):
            p = jnp.asarray(pos)
            if predict:
                n = horizon_steps(cfg.predict_horizon_s, cfg)
                p = predict_kinematics(p, jnp.asarray(speed),
                                       jnp.asarray(accel), n, cfg)[0]
            p = np.asarray(p, np.float64)
            d = np.abs(p[:, None] - rsu[None, :])
            d = np.sort(np.where(live, np.minimum(d, L - d), np.inf), axis=1)
            d3 = np.sqrt(d[:, 0] ** 2 + 15.0**2 + 5.0**2)
            snr = np.asarray(snr_from_dist(jnp.asarray(d3, jnp.float32), cfg))
        near = (d[:, 1] - d[:, 0] < 1.0) | (
            np.abs(snr - float(cfg.snr_min_db)) < 0.01
        )
        if not near.any():
            return pos
        pos = np.where(near, np.mod(pos + 3.0, L), pos).astype(np.float32)
    raise AssertionError("could not place vehicles clear of ties")


def check_kernels(phase: Phase, s: dict) -> None:
    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng(0)
    on_cpu = lambda *xs: jax.device_put(xs, cpu)
    hp = server_hp(phase.fl)
    hpkw = dict(eta=hp.eta, beta1=hp.beta1, beta2=hp.beta2, tau=hp.tau)
    f32 = lambda *shape: rng.uniform(0.5, 1.5, shape).astype(np.float32)

    # rttg_latency: both passes, attachment ids out, on the last scenario
    cfg = scenario_params(
        scenario_config(phase.scenarios[-1], num_vehicles=s["N"])
    )
    N, L = s["N"], float(cfg.ring_length_m)
    speed = rng.uniform(5.0, 25.0, N).astype(np.float32)
    accel = rng.normal(0.0, 0.8, N).astype(np.float32)
    t, mb = np.float32(450.0), np.float32(4.0 * s["P"])
    for predict in (True, False):
        pos = clear_of_ties(rng.uniform(0.0, L, N).astype(np.float32),
                            speed, accel, cfg, predict, cpu)
        args = (pos, speed, accel, t, mb, None, cfg)
        got = ops.rttg_latency_auto(*args, predict=predict, want_rid=True)
        want = _ref_rttg(*args, predict, want_rid=True)
        name = f"rttg_latency(predict={predict})"
        compare(phase.name, name + " latency", got[0], want[0], RTTG_RTOL)
        compare_exact(phase.name, name + " connected", got[1], want[1])
        compare_exact(phase.name, name + " rsu id", got[2], want[2])

    P, rows = s["P"], s["rows"]
    if "server_update_buffered" in phase.kernels:
        K, Kb = s["K"], s["Kb"]
        w = f32(K) / K
        args = (f32(K, P) * 1e-2, w, f32(Kb, P) * 1e-2, f32(Kb) / Kb,
                f32(P), f32(P) * 1e-2, f32(P) * 1e-4)
        for agg in phase.aggregators:
            tail = (np.int32(AGGREGATOR_ORDER.index(agg)), np.int32(1),
                    np.bool_(True))
            got = ops.server_update_buffered_auto(*args, *tail, **hpkw)
            with jax.default_device(cpu):
                want = ref.server_update_buffered(*on_cpu(*args, *tail),
                                                  **hpkw)
            for out, g, wv in zip(("params", "m", "v"), got, want):
                compare(phase.name, f"server_update_buffered({agg}) {out}",
                        g, wv, reduce_rtol(K + Kb))
    if "rsu_reduce" in phase.kernels:
        # fp32 partials: the engine's bf16 ones would round away the fp32
        # accumulation this bound checks
        K, R = s["block"], s["R"]
        u = jnp.asarray(f32(K, P) * 1e-2, rows)
        w, rid = f32(K), rng.permutation(np.arange(K) % R).astype(np.int32)
        got = ops.rsu_reduce_auto(u, w, rid, R)
        with jax.default_device(cpu):
            want = ref.rsu_reduce(*on_cpu(u, w, rid), R)
        compare(phase.name, "rsu_reduce partials", got[0], want[0],
                reduce_rtol(K))
        compare(phase.name, "rsu_reduce mass", got[1], want[1], reduce_rtol(K))
    if "fedavg_reduce" in phase.kernels:
        R = s["R"]
        u, w = jnp.asarray(f32(R, P) * 1e-2, rows), f32(R)
        got = ops.fedavg_reduce_auto(u, w)
        with jax.default_device(cpu):
            want = ref.fedavg_reduce(*on_cpu(u, w))
        compare(phase.name, "fedavg_reduce", got, want, reduce_rtol(R))


# ---- four chips: the sharded grid against the vmapped one -----------------

# make_test_set's 2,000 images: test_acc moves in steps of 1/2000
TEST_IMAGES = 2_000
# The sharded program tiles 5 lanes per chip and the vmapped one 18 on one
# chip, so XLA orders their f32 accumulations differently: the parameters
# agree to float noise, but a test image whose top two logits lie that
# close flips.  On a v5e one or two of the 2,000 flipped in 7 of 18 lanes.
FLIPPED_IMAGES = 2


def run_four_chips() -> None:
    """The paper grid (18 lanes, padded to 20) sharded over a 4-chip grid
    mesh, against the same grid vmapped on one of the chips."""
    if len(jax.devices()) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, found "
                             f"{len(jax.devices())}")
    if PAPER.lanes % 4 == 0:
        raise AssertionError("the four-chip grid must need padding")
    kw = PAPER.run_kwargs()
    sharded = PAPER.engine(mesh=make_grid_mesh())
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        rs = sharded.run_grid(**kw)
        jax.block_until_ready(rs.metrics)
        times.append(time.perf_counter() - t0)
    shards = sharded.grid_shards()
    say("four-chips", f"smoke timing, not a benchmark metric: sharded "
                      f"{PAPER.lanes} lanes, padded to "
                      f"{-(-PAPER.lanes // shards) * shards}, on {shards} "
                      f"chips, first run_grid {times[0]:.3f} s, steady "
                      f"{times[1]:.3f} s")
    t0 = time.perf_counter()
    rb = PAPER.engine().run_grid(**kw)
    jax.block_until_ready(rb.metrics)
    say("four-chips", f"smoke timing, not a benchmark metric: vmapped on one "
                      f"chip, first run_grid {time.perf_counter() - t0:.3f} s")
    for r in (rs, rb):
        check_metrics("four-chips", r.metrics, PAPER.rounds)
    if rs.runs != rb.runs:
        raise AssertionError("sharded and vmapped grids label lanes apart")
    apart = []
    for field in rb.metrics._fields:
        a = np.asarray(getattr(rs.metrics, field), np.float64)
        b = np.asarray(getattr(rb.metrics, field), np.float64)
        m = np.isfinite(b)
        if not np.array_equal(np.isfinite(a), m):
            raise AssertionError(f"four-chips: {field} finite in other places")
        diff = np.abs(a[m] - b[m])
        say("four-chips", f"{field}: max abs difference "
                          f"{np.max(diff, initial=0.0):.3e}")
        if field == "test_acc":
            ok = np.all(np.round(diff * TEST_IMAGES) <= FLIPPED_IMAGES)
        else:  # the tolerance tests/test_engine.py holds the same parity to
            ok = np.allclose(a[m], b[m], rtol=2e-4, atol=1e-5)
        if not ok:
            apart.append(field)
    if apart:
        raise AssertionError(f"four-chips: sharded and vmapped grids differ "
                             f"in {apart}")
    say("four-chips", f"sharded == vmapped over {len(rb.runs)} lanes: within "
                      f"rtol 2e-4 / atol 1e-5, test_acc within "
                      f"{FLIPPED_IMAGES} of {TEST_IMAGES} test images")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-vmapped grid on 4 chips")
    args = ap.parse_args(argv)
    cache = use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found {dev.platform}")
    print(f"smoke: {len(jax.devices())} x {dev.device_kind}, compile cache "
          f"{cache}", flush=True)
    if args.four_chips:
        run_four_chips()
    else:
        say("paper", f"strategies cut to fit one chip: {', '.join(PAPER_CUT)}"
                     f" ({PAPER.lanes} lanes run)")
        for phase in (PAPER, FLEET):
            run_phase(phase)
        for phase in (PAPER, FLEET):
            check_kernels(phase, kernel_shapes(phase))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
