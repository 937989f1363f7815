"""One benchmark run of one cell of the FL experiment engine on TPU chips.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json.  It names a
configuration (a file under bench/configs/: the model, its client-model
module under bench/models/ and the shapes, every FLConfig field, the
precision of matrix products) and a traffic mix
(bench/workloads/<traffic>.json: the grid axes, rounds per sweep, eval
cadence, warm-up, and how many lanes the correctness check samples).  A run:

1. keeps JAX's persistent compilation cache in `$JAX_COMPILATION_CACHE_DIR`,
   or else in the checkout's `.jax_cache/`;
2. fails, printing no result, unless JAX finds as many TPU chips as the
   cell asks for;
3. builds the cell's `ExperimentEngine` (over a grid mesh of all its chips
   when it asks for more than one) and runs one sweep of `run_grid` as
   warm-up, which ends the set-up time;
4. without `--trace`, runs whole sweeps back to back, each with a fresh
   experiment seed drawn from `--seed` and the sweep's index, each ending in
   `block_until_ready`, and starts none once `--seconds` have passed; with
   `--trace 1`, traces two such sweeps instead, reduces the trace and
   reports the cell's per-layer metrics;
5. reads the peak device memory (`peak_bytes`), then checks lanes drawn
   from the seed against `bench/reference.py` (see `bench/check.py`);
6. prints the numbers compared and their limits as its last lines on
   standard error, and one JSON object as the last line of standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up time runs from here

import argparse  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, models, reference, trace  # noqa: E402

TRACED_SWEEPS = 2
GIB = 2.0 ** 30


# ---- the cell, from data files ---------------------------------------------

def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration, client model, traffic, limits and
    metric specs."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in spec["configs"]}
    cell["config_spec"] = configs[cell["config"]]
    cell["cfg"] = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    family = cell["cfg"].get("client_model")
    if not isinstance(family, str) or not re.fullmatch(r"\w+", family):
        raise SystemExit(f"bench: configuration {cell['config']!r} names no client "
                         f"model (`client_model`: {family!r})")
    cell["model_file"] = os.path.join(root, "bench", "models", family + ".py")
    if not os.path.isfile(cell["model_file"]):
        raise SystemExit(f"bench: no client model bench/models/{family}.py")
    cell["model"] = models.load(cell["model_file"])
    cell["mix"] = load_json(os.path.join(root, "bench", "workloads",
                                         cell["traffic"] + ".json"))
    limits = os.path.join(root, "bench", "limits", name + ".json")
    cell["limits"] = load_json(limits) if os.path.exists(limits) else None
    here = lambda m: name in m.get("workloads", [name])
    cell["end_to_end"] = [m for m in spec["end_to_end"] if here(m)]
    cell["per_layer"] = [m for m in spec["per_layer"] if here(m)]
    cell["root"] = root
    return cell


def metric_reader(name: str, root: str = ROOT):
    """`read(ctx)` of bench/metrics/<name>.py."""
    return models.load(os.path.join(root, "bench", "metrics", name + ".py")).read


def sweep_seed(seed: int, index: int) -> int:
    """The experiment seed of sweep ``index`` (-1 is the warm-up)."""
    digest = hashlib.sha256(f"{seed}/{index}".encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


def lanes_of(mix: dict) -> int:
    return len(mix["strategies"]) * len(mix["aggregators"]) * len(mix["scenarios"])


# ---- the device -------------------------------------------------------------

def use_compile_cache(root: str = ROOT) -> str:
    """The persistent compilation cache: `$JAX_COMPILATION_CACHE_DIR`, which
    JAX reads itself, or else `.jax_cache/` at the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def check_device(chips: int):
    """The devices of the run: ``chips`` TPU chips, or exit with no result."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


# ---- the system under test ---------------------------------------------------

def model_config(cell: dict):
    """The program's `ModelConfig` of the cell, held by the client model's
    shape check to what the configuration states."""
    from repro.configs import get_config

    model = get_config(cell["cfg"]["model"])
    try:
        cell["model"].check_shapes(model, cell["cfg"]["shapes"])
    except ValueError as e:
        raise SystemExit(f"bench: {e}") from None
    return model


def build_engine(cell: dict, devices):
    from repro.config import FLConfig
    from repro.fl.engine import ExperimentEngine
    from repro.launch.mesh import make_grid_mesh

    cfg, mix = cell["cfg"], cell["mix"]
    model = model_config(cell)
    mesh = make_grid_mesh(len(devices)) if len(devices) > 1 else None
    return ExperimentEngine(
        model, FLConfig(**cfg["fl"]), cfg["dataset"],
        strategies=tuple(mix["strategies"]), aggregators=tuple(mix["aggregators"]),
        warmup=mix["warmup"], mesh=mesh,
    )


def sweep(eng, cell: dict, seed: int):
    """One `run_grid` sweep, not yet blocked on, its matrix products at the
    precision the configuration states."""
    mix = cell["mix"]
    with jax.default_matmul_precision(cell["cfg"]["matmul_precision"]):
        return eng.run_grid(seeds=(seed,), scenarios=tuple(mix["scenarios"]),
                            rounds=mix["rounds"], eval_every=mix["eval_every"])


class Recorder:
    """Stands in for a jitted grid program and keeps the shapes of its first
    call, so that its compiled program (its text, its memory) can be read
    after the window."""

    def __init__(self, fn):
        self.fn, self.args, self.kwargs = fn, None, None

    def __call__(self, *args, **kwargs):
        if self.args is None:
            self.precision = jax.config.jax_default_matmul_precision
            self.args = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    # an uncommitted argument goes where the program puts it
                    sharding=x.sharding if getattr(x, "committed", False) else None),
                args)
            self.kwargs = kwargs
        return self.fn(*args, **kwargs)

    def compiled(self):
        """The compiled program of the first call (lowered anew; the
        executable itself comes from the persistent cache)."""
        if not hasattr(self, "_compiled"):
            with jax.default_matmul_precision(self.precision):
                self._compiled = self.fn.lower(*self.args, **self.kwargs).compile()
        return self._compiled


def record_program(eng):
    """Wrap whichever grid program the engine will call in a `Recorder`."""
    rec = {"vmapped": Recorder(eng._grid_fn)}
    eng._grid_fn = rec["vmapped"]
    build = eng._build_sharded

    def build_and_record(*a, **k):
        rec["sharded"] = Recorder(build(*a, **k))
        return rec["sharded"]

    eng._build_sharded = build_and_record
    return rec


# ---- one run ----------------------------------------------------------------

def window(eng, cell, seed, seconds):
    """Sweeps back to back until ``seconds`` have passed -> ([(seed,
    result)], the seconds from the window's start to each sweep's end)."""
    done, ends, t0 = [], [], time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        s = sweep_seed(seed, len(done))
        res = sweep(eng, cell, s)
        jax.block_until_ready(res.metrics)
        done.append((s, res))
        ends.append(time.perf_counter() - t0)
    return done, ends


def traced_window(eng, cell, seed, rec):
    """``TRACED_SWEEPS`` sweeps under the profiler, each harness step a host
    span -> (sweeps, host seconds of each `run_grid` call, reduced trace)."""
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        done, host_s = [], []
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the harness's spans, not every call
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp, profiler_options=opts)
        for i in range(TRACED_SWEEPS):
            with jax.profiler.TraceAnnotation("bench.prepare"):
                s = sweep_seed(seed, i)
            with jax.profiler.TraceAnnotation("bench.run_grid"):
                t0 = time.perf_counter()
                res = sweep(eng, cell, s)
                host_s.append(time.perf_counter() - t0)
            with jax.profiler.TraceAnnotation("bench.block"):
                jax.block_until_ready(res.metrics)
            done.append((s, res))
        jax.profiler.stop_trace()
        hlo = program(rec).compiled().as_text()
        red = trace.reduce_trace(trace.find_xplane(tmp), trace.kernel_instructions(hlo),
                                 trace.op_paths(hlo))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return done, host_s, red


def failed_lanes(metrics) -> int:
    """Lanes with a non-finite round economics number in some round."""
    bad = np.zeros(np.asarray(metrics.sim_time).shape[0], bool)
    for field in ("sim_time", "duration", "mean_pred_latency", "mean_real_latency"):
        bad |= ~np.all(np.isfinite(np.asarray(getattr(metrics, field))), axis=1)
    return int(bad.sum())


def program(rec) -> Recorder:
    """The grid program the window drove."""
    return rec.get("sharded") or rec["vmapped"]


def peak_bytes(devices, rec) -> int:
    """The peak device memory of the process on its fullest chip: the
    allocator's peak of the buffers it holds, plus the grid program's
    temporaries, which the TPU runtime reserves for the program outside
    that count (the compiler's `temp_size_in_bytes`, per chip)."""
    held = max(d.memory_stats()["peak_bytes_in_use"] for d in devices)
    return held + program(rec).compiled().memory_analysis().temp_size_in_bytes


def device_info(devices, peak_bytes):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak_bytes)}


def run(args, root: str = ROOT) -> dict:
    cell = load_cell(args.workload, root)
    if cell["limits"] is None:
        raise SystemExit(f"bench: no bench/limits/{args.workload}.json")
    cache = use_compile_cache(root)
    devices = check_device(cell["chips"])
    peaks = load_json(os.path.join(root, "bench", "peaks.json"))
    kind = devices[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in bench/peaks.json")
    mix = cell["mix"]
    t_device = time.perf_counter()
    eng = build_engine(cell, devices)
    rec = record_program(eng)
    t_engine = time.perf_counter()
    warm = sweep(eng, cell, sweep_seed(args.seed, -1))
    jax.block_until_ready(warm.metrics)
    setup_s = time.perf_counter() - T_START
    print(f"bench: {cell['name']} on {len(devices)} x {kind}, compile cache {cache}, "
          f"set-up {setup_s:.3f} s: imports and device {t_device - T_START:.3f}, engine "
          f"{t_engine - t_device:.3f}, first sweep {T_START + setup_s - t_engine:.3f}",
          file=sys.stderr, flush=True)

    lanes, rounds = lanes_of(mix), mix["rounds"]
    metrics = {}
    if args.trace:
        done, host_s, red = traced_window(eng, cell, args.seed, rec)
        ctx = {"trace": red, "sweeps": len(done), "run_grid_s": host_s,
               "chips": len(devices), "peak": peaks[kind], "cfg": cell["cfg"],
               "model": cell["model"],
               "mix": mix, "lanes": lanes, "scenarios": reference.SCENARIOS}
        for m in cell["per_layer"]:
            value = metric_reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        done, ends = window(eng, cell, args.seed, args.seconds)
        metrics["lane_rounds_per_s"] = {
            "value": lanes * rounds * len(done) / ends[-1], "unit": "rounds/s"}
        print(f"bench: {len(done)} sweeps, ending at {[round(t, 3) for t in ends]} s "
              "into the window", file=sys.stderr)
    peak = peak_bytes(devices, rec)
    if not args.trace:
        metrics["peak_hbm_gib"] = {"value": peak / GIB, "unit": "GiB"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    attempted = lanes * len(done)
    failed = sum(failed_lanes(res.metrics) for _, res in done)
    # the check runs once the program's state is freed
    sample = check.draw(args.seed, done, lanes)
    del eng, warm, done, rec
    checked = check.compare(cell, sample)
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checked.values())
    for name, c in checked.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device_info(devices, peak)}
    if args.trace:
        busy = sum(d["busy_s"] for d in red["devices"]) / len(red["devices"])
        out["device"].update(busy_s=busy, window_s=red["window_s"])
        out["breakdown"] = trace.breakdown(red)
    out["checked"] = checked
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: str = ROOT) -> None:
    out = run(parse(argv), root)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
