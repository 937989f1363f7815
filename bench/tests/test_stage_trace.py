"""The reduction of a trace by the program's own spans and stage scopes.

`data/grid2-stages.xplane.pb.gz` is a trace recorded on a TPU v5e the way
`data/grid2.xplane.pb.gz` was (`mnist-paper-grid` cut to two lanes,
contextual x fedavg/fedadam on the ring, one round a sweep, two sweeps
traced with the harness's spans and profiler options, `stage_trace.record`),
from the program with its `engine.` spans and `fl.<stage>` scopes.
`data/grid2-stages.json` holds its grid program's module name, the stage of
each instruction the trace runs (`stage_of_instructions`) and its kernels.
The older trace, from the program before the spans and scopes, is read too:
the reduction must work on what such a program records.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import stage_trace, trace  # noqa: E402
from repro.utils.tracing import STAGES  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "grid2-stages.xplane.pb.gz")
OLD_TRACE = os.path.join(DATA, "grid2.xplane.pb.gz")
SWEEPS = 2


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "grid2-stages.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(recorded):
    return stage_trace.reduce_stages(TRACE, recorded["stages"], recorded["module"])


def test_instructions_take_the_first_stage_component_of_their_op_name():
    hlo = "\n".join([
        'HloModule jit__grid, is_scheduled=true',
        '',
        '%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {',
        '  %p = (s32[], f32[8]) parameter(0)',
        '  %dynamic-update-slice.4 = f32[8] dynamic-update-slice(%p, %p)',
        '}',
        '',
        'ENTRY %main.2 (a: f32[8]) -> f32[8] {',
        '  %a = f32[8] parameter(0)',
        '  %fusion.7 = f32[8] fusion(%a), kind=kLoop, metadata={op_name='
        '"jit(_grid)/vmap()/while/body/closed_call/round/fl.train/jit(train_cohort)/'
        'transpose(jvp())/dot_general"}',
        '  %copy.2 = f32[8] copy(%fusion.9), metadata={op_name="jit(_grid)/fl.init/'
        'fl.eval/add"}',
        '  %add.3 = f32[8] add(%a, %a), metadata={op_name="jit(_grid)/nfl.train/add"}',
        '  %squeeze.5 = f32[8] squeeze(%a), metadata={op_name="jit(_grid)/vmap(fl.warmup)/x"}',
        '  %while.6 = (s32[], f32[8]) while(%a), body=%body.1, metadata={op_name='
        '"jit(_grid)/round/fl.server/scatter"}',
        '  %copy.8 = f32[8] copy(%add.3, %squeeze.5)',
        '  ROOT %copy.9 = f32[8] copy(%a)',
        '}',
    ])
    assert stage_trace.stage_of_instructions(hlo) == {
        "fusion.7": "train", "copy.2": "init", "squeeze.5": "warmup",
        # compiler-made: by the loop that holds it, by the value it copies
        "while.6": "server", "p": "server", "dynamic-update-slice.4": "server",
        "copy.8": "warmup"}
    assert stage_trace.module_name(hlo) == "jit__grid"
    with pytest.raises(ValueError):
        stage_trace.module_name("%add.3 = f32[8] add(%c, %d)")


def test_stripping_keeps_ops_and_drops_names_and_source_tables():
    a = "\n".join([
        "HloModule jit__grid", "", "FileNames", '1 "a.py"', "", "StackFrames",
        "1 {file_location_id=1}", "",
        '%add.3 = f32[8] add(%c, %d), metadata={op_name="jit(_grid)/fl.server/add"'
        " source_line=3}",
    ])
    b = a.replace("fl.server/", "").replace('"a.py"', '"b.py"').replace("=3}", "=9}")
    assert stage_trace.strip_metadata(a) == stage_trace.strip_metadata(b)
    assert "%add.3 = f32[8] add(%c, %d)" in stage_trace.strip_metadata(a)
    assert stage_trace.strip_metadata(a) != stage_trace.strip_metadata(
        a.replace("add(%c, %d)", "add(%d, %c)"))


@pytest.mark.parametrize("path", [TRACE, OLD_TRACE], ids=["stages", "before-stages"])
def test_window_and_busy_time_are_the_harness_reading(path, recorded):
    red = stage_trace.reduce_stages(path, recorded["stages"], recorded["module"])
    plain = trace.reduce_trace(path, recorded["kernels"])
    assert red["window_s"] == plain["window_s"]
    assert [d["busy_s"] for d in red["devices"]] == [d["busy_s"] for d in plain["devices"]]
    for d in red["devices"]:
        idle = sum(s for s, _ in d["gaps"])
        assert d["busy_s"] + idle == pytest.approx(red["window_s"], rel=1e-9)


def test_the_program_spans_lie_in_the_harness_spans(reduced):
    assert set(reduced["spans"]) == {"bench.prepare", "bench.run_grid", "bench.block",
                                     "engine.lanes", "engine.stack", "engine.launch"}
    engine = sum(v for k, v in reduced["spans"].items() if k.startswith("engine."))
    assert 0 < engine <= reduced["spans"]["bench.run_grid"]


def test_every_stage_has_device_time_and_they_cover_the_grid_program(reduced):
    for d in reduced["devices"]:
        assert set(d["stage_s"]) == set(STAGES)
        assert all(s > 0 for s in d["stage_s"].values())
        assert 0 < d["grid_s"] <= d["busy_s"]
        assert sum(d["stage_s"].values()) >= 0.9 * d["grid_s"]


def test_launches_inside_run_grid_are_counted(reduced):
    summary = stage_trace.summary(reduced, SWEEPS)
    # the grid program once a sweep, and the eager launches of run_grid
    assert summary["launches_per_sweep"] > 1
    assert summary["launches_per_sweep"] == sum(
        d["launches"] for d in reduced["devices"]) / SWEEPS


def test_idle_time_in_run_grid_is_named_by_the_engine_spans(reduced):
    names = {name for d in reduced["devices"] for _, name in d["gaps"]}
    assert names <= {"bench.prepare", "bench.run_grid", "bench.block", "engine.lanes",
                     "engine.stack", "engine.launch"}
    assert stage_trace.summary(reduced, SWEEPS)["run_grid_idle_in_engine_spans"] >= 0.9


def test_a_trace_without_scopes_reads_no_stage(recorded):
    red = stage_trace.reduce_stages(OLD_TRACE, {}, recorded["module"])
    summary = stage_trace.summary(red, SWEEPS)
    assert summary["stage_ms"] == {} and summary["stage_coverage"] == 0.0
    assert summary["grid_ms"] > 0 and summary["launches_per_sweep"] > 1
    assert set(summary["idle_ms"]) <= {"bench.prepare", "bench.run_grid", "bench.block"}
