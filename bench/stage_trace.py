"""Where a traced sweep's time goes, by the program's own spans and scopes.

    python3 bench/stage_trace.py --workload <cell> --seed <n> [--sweeps 4] [--keep <dir>]

The program marks its host phases with `engine.` spans (`engine.lanes`,
`engine.stack`, `engine.launch` in `ExperimentEngine.run_grid`) and the
ops of its compiled grid program with `fl.<stage>` named scopes
(`repro.utils.tracing`).  `reduce_stages` reads a profiler trace of
sweeps run the way `bench/run.py --trace 1` runs them, with the same
window (first to last `bench.` span) and busy time, and adds:

* each idle gap named by the innermost `bench.` or `engine.` span the
  host was in when it began;
* each grid-program op's own device time credited to the first
  `fl.<stage>` component of its `op_name` (an op is a grid-program op when
  it lies inside an `XLA Modules` event of the grid program), and the
  share of the grid program's device time that carries a stage;
* the device programs launched inside `bench.run_grid` spans (the
  `XLA Modules` events begun there, summed over chips).

The command builds the cell as `bench/run.py` does, warms it up, times
``--sweeps`` untraced sweeps, traces two more, and prints one JSON object:
per-sweep host time of each span, launches, device time of each stage,
stage coverage, idle time by span, set-up by compile phase and the
compilations of each window (`repro.utils.tracing.compile_counts`), and
the untraced and traced sweeps' seconds.  ``--keep`` keeps the trace
(`<cell>.xplane.pb.gz`) and the grid program's compiled HLO
(`<cell>.hlo.txt.gz`) in a directory, to read again or to compare two
trees' programs op for op (`strip_metadata`).  It runs on the TPU chips
the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up time runs from here, as in bench/run.py

import argparse  # noqa: E402
import collections  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import run, trace  # noqa: E402
from repro.utils.tracing import compile_counts  # noqa: E402

SPAN_PREFIXES = (trace.SPAN_PREFIX, "engine.")
STAGE_RE = re.compile(r"(?:^|[/(])fl\.(\w+)(?=[/)]|$)")
INSTRUCTION_RE = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
COMPUTATION_RE = re.compile(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")


# ---- reading a trace ----------------------------------------------------------

def load(path: str):
    """The `ProfileData` of an `.xplane.pb` file, or of its gzip."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def stage_of_instructions(hlo_text: str) -> Dict[str, str]:
    """{instruction name: stage} of the compiled HLO text.

    An instruction takes the first `fl.<stage>` component of its `op_name`
    (a component a transform wraps, `vmap(fl.warmup)`, counts).  One with
    no `op_name` at all was made by the compiler: inside a fusion, loop or
    branch it takes the stage of the instruction that calls its
    computation, and elsewhere (a copy, a layout change) the stage of the
    first of its operands that has one.  Others have no stage.
    """
    lines = hlo_text.splitlines()
    computations = {m.group(1) for m in map(COMPUTATION_RE.match, lines) if m}
    op_names, operands, computation_of, caller = {}, {}, {}, {}
    computation = None
    for line in lines:
        head = COMPUTATION_RE.match(line)
        if head:
            computation = head.group(1)
            continue
        name = INSTRUCTION_RE.match(line)
        if not name:
            continue
        name = name.group(1)
        op = re.search(r'op_name="([^"]*)"', line)
        op_names[name] = op.group(1) if op else None
        computation_of[name] = computation
        operands[name] = []
        for token in re.findall(r"%([\w.\-]+)", line.split("=", 1)[1]):
            if token in computations:
                caller.setdefault(token, name)
            else:
                operands[name].append(token)

    def own(name):
        found = STAGE_RE.search(op_names.get(name) or "")
        return found.group(1) if found else None

    def stage(name, depth=0):
        if op_names[name] is not None:
            return own(name)
        held_by = caller.get(computation_of[name])
        if held_by is not None and depth < 32:
            return stage(held_by, depth + 1)
        return next(filter(None, map(own, operands[name])), None)

    return {name: s for name in op_names if (s := stage(name))}


def module_name(hlo_text: str) -> str:
    """The name of the compiled HLO module (`jit__grid`), which its
    `XLA Modules` events carry."""
    m = re.match(r"\s*HloModule\s+([\w.\-]+)", hlo_text)
    if not m:
        raise ValueError("not the text of a compiled HLO module")
    return m.group(1)


def strip_metadata(hlo_text: str) -> str:
    """The compiled HLO text without its `metadata={...}` attributes and
    its source tables (file, function and stack-frame names): what two
    programs that differ only in names and source lines share."""
    out, table = [], False
    for line in hlo_text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            table = True
        elif table and not line.strip():
            table = False
        elif not table:
            out.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return "\n".join(out) + "\n"


def host_spans(data):
    """(start_ns, end_ns, name) of every `bench.` and `engine.` span."""
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    return sorted(spans)


def _inside(t, intervals) -> bool:
    return any(s <= t < e for s, e in intervals)


def reduce_stages(path: str, stages: Dict[str, str], module: str) -> dict:
    """Reduce the trace at ``path`` by the program's spans and scopes.

    ``stages`` is `stage_of_instructions` of the grid program's compiled
    HLO and ``module`` its `module_name`.  -> {"window_s", "spans": {name:
    s}, "devices": [{"name", "busy_s", "grid_s", "stage_s": {stage: s},
    "launches", "gaps": [(s, span)]}]}: the window and busy time are
    `trace.reduce_trace`'s; ``grid_s`` is the own time of the grid
    program's ops, ``stage_s`` the part of it under each stage; a gap is
    named by the innermost span at its start.
    """
    data = load(path)
    spans = host_spans(data)
    bench_spans = [s for s in spans if s[2].startswith(trace.SPAN_PREFIX)]
    if not bench_spans:
        raise RuntimeError("the trace holds no bench. host span")
    w0, w1 = bench_spans[0][0], max(e for _, e, _ in bench_spans)
    span_s = collections.Counter()
    for s, e, name in spans:
        span_s[name] += (e - s) * 1e-9
    run_grid = [(s, e) for s, e, name in spans if name == "bench.run_grid"]

    def span_at(t):
        inside = [(e - s, name) for s, e, name in spans if s <= t < e]
        return min(inside)[1] if inside else "outside any span"

    devices = []
    for plane in trace.device_planes(data):
        lines = {line.name: list(line.events) for line in plane.lines}
        grid = [(ev.start_ns, ev.end_ns) for ev in lines.get("XLA Modules", [])
                if ev.name.startswith(module + "(")]
        launches = sum(_inside(ev.start_ns, run_grid)
                       for ev in lines.get("XLA Modules", []))
        events = []
        for ev in lines.get("XLA Ops", []):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e > s:
                events.append((s, e, trace.op_name(ev.name), _inside(ev.start_ns, grid)))
        grid_s, stage_s = 0.0, collections.Counter()
        for i, ns in trace._self_times(events).items():
            _, _, name, in_grid = events[i]
            if in_grid:
                grid_s += ns * 1e-9
                if name in stages:
                    stage_s[stages[name]] += ns * 1e-9
        busy = trace._union([ev[:2] for ev in events])
        gaps, prev = [], w0
        for s, e in busy + [(w1, w1)]:
            if s > prev:
                gaps.append(((s - prev) * 1e-9, span_at(prev)))
            prev = max(prev, e)
        devices.append({
            "name": plane.name,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "grid_s": grid_s,
            "stage_s": dict(stage_s),
            "launches": launches,
            "gaps": sorted(gaps, reverse=True),
        })
    if not devices:
        raise RuntimeError("the trace holds no device plane")
    return {"window_s": (w1 - w0) * 1e-9, "devices": devices, "spans": dict(span_s)}


def summary(red: dict, sweeps: int) -> dict:
    """Per-sweep numbers of a `reduce_stages` result: host ms of each span,
    launches (summed over chips), device ms of each stage (mean over
    chips), stage coverage of the grid program's device time (the least
    over chips), idle ms by the span a gap began in (mean over chips) and
    the share of the idle time inside `bench.run_grid` spans that falls in
    gaps named by an `engine.` span."""
    devs, per = red["devices"], 1e3 / sweeps
    idle = collections.Counter()
    for d in devs:
        for s, name in d["gaps"]:
            idle[name] += s * per / len(devs)
    in_run_grid = sum(v for k, v in idle.items()
                      if k == "bench.run_grid" or k.startswith("engine."))
    in_engine = sum(v for k, v in idle.items() if k.startswith("engine."))
    stages = sorted({k for d in devs for k in d["stage_s"]})
    return {
        "window_ms": red["window_s"] * per,
        "busy_ms": sum(d["busy_s"] for d in devs) * per / len(devs),
        "span_ms": {k: v * per for k, v in sorted(red["spans"].items())},
        "launches_per_sweep": sum(d["launches"] for d in devs) / sweeps,
        "grid_ms": sum(d["grid_s"] for d in devs) * per / len(devs),
        "stage_ms": {k: sum(d["stage_s"].get(k, 0.0) for d in devs) * per / len(devs)
                     for k in stages},
        "stage_coverage": min((sum(d["stage_s"].values()) / d["grid_s"]
                               if d["grid_s"] else 0.0) for d in devs),
        "idle_ms": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "run_grid_idle_in_engine_spans": in_engine / in_run_grid if in_run_grid else None,
    }


# ---- one command on the chip ---------------------------------------------------

def counts_between(a, b):
    return {k: {"count": b[k]["count"] - a[k]["count"],
                "seconds": b[k]["seconds"] - a[k]["seconds"]} for k in b}


def record(eng, cell, seed: int, directory: str, sweeps: int = run.TRACED_SWEEPS):
    """``sweeps`` sweeps under the profiler with `bench/run.py`'s profiler
    options and host spans -> (the `.xplane.pb` path, each sweep's host
    seconds from its `bench.prepare` to the end of its `bench.block`)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    seconds = []
    jax.profiler.start_trace(directory, profiler_options=opts)
    for i in range(sweeps):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.prepare"):
            s = run.sweep_seed(seed, i)
        with jax.profiler.TraceAnnotation("bench.run_grid"):
            res = run.sweep(eng, cell, s)
        with jax.profiler.TraceAnnotation("bench.block"):
            jax.block_until_ready(res.metrics)
        seconds.append(time.perf_counter() - t0)
    jax.profiler.stop_trace()
    return trace.find_xplane(directory), seconds


def keep(directory: str, name: str, path: str, hlo: str) -> None:
    """Gzip the trace at ``path`` and the compiled ``hlo`` into ``directory``."""
    os.makedirs(directory, exist_ok=True)
    with open(path, "rb") as f, gzip.open(
            os.path.join(directory, name + ".xplane.pb.gz"), "wb") as g:
        shutil.copyfileobj(f, g)
    with gzip.open(os.path.join(directory, name + ".hlo.txt.gz"), "wt") as g:
        g.write(hlo)


def measure(args) -> dict:
    cell = run.load_cell(args.workload)
    run.use_compile_cache()
    devices = run.check_device(cell["chips"])
    eng = run.build_engine(cell, devices)
    rec = run.record_program(eng)
    warm = run.sweep(eng, cell, run.sweep_seed(args.seed, -1))
    jax.block_until_ready(warm.metrics)
    setup_s = time.perf_counter() - T_START
    at_setup = compile_counts()

    untraced = []
    for i in range(args.sweeps):
        t0 = time.perf_counter()
        res = run.sweep(eng, cell, run.sweep_seed(args.seed + 1, i))
        jax.block_until_ready(res.metrics)
        untraced.append(time.perf_counter() - t0)
    at_untraced = compile_counts()

    tmp = tempfile.mkdtemp(prefix="stage-trace-")
    try:
        path, traced = record(eng, cell, args.seed, tmp)
        at_traced = compile_counts()
        hlo = run.program(rec).compiled().as_text()
        red = reduce_stages(path, stage_of_instructions(hlo), module_name(hlo))
        plain = trace.reduce_trace(path, trace.kernel_instructions(hlo))
        if args.keep:
            keep(args.keep, cell["name"], path, hlo)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    out = {
        "workload": cell["name"], "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "setup_s": setup_s, "setup_compile": at_setup,
        "untraced_window_compile": counts_between(at_setup, at_untraced),
        "traced_window_compile": counts_between(at_untraced, at_traced),
        "untraced_sweep_s": untraced, "traced_sweep_s": traced,
        "untraced_sweep_median_s": statistics.median(untraced),
        "traced_sweep_median_s": statistics.median(traced),
        "plain_window_ms": plain["window_s"] * 1e3 / len(traced),
        "plain_busy_ms": sum(d["busy_s"] for d in plain["devices"]) * 1e3
        / len(plain["devices"]) / len(traced),
    }
    out.update(summary(red, len(traced)))
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sweeps", type=int, default=4,
                    help="untraced sweeps timed before the traced ones")
    ap.add_argument("--keep", help="keep the trace and the compiled HLO here")
    print(json.dumps(measure(ap.parse_args(argv))), flush=True)


if __name__ == "__main__":
    main()
