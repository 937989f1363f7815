"""The trace reduction on a small trace recorded on a TPU v5e chip.

`data/grid2.xplane.pb.gz` is the two traced sweeps of a `bench/run.py
--trace 1` run of `mnist-paper-grid` cut to two lanes (contextual x
fedavg/fedadam on the ring) and one round a sweep, recorded on a TPU v5e
with the harness's own profiler options; `data/grid2.kernels.json` is the
join of its compiled program's `tpu_custom_call` instructions to their jit
wrappers.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(DATA, "grid2.kernels.json")) as f:
        kernels = json.load(f)
    return trace.reduce_trace(os.path.join(DATA, "grid2.xplane.pb.gz"), kernels)


def test_kernel_instructions_are_named_by_their_jit_wrapper():
    hlo = "\n".join([
        '  %rsu_reduce.8 = (bf16[10,256]) custom-call(%a), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(_grid)/while/body/jit(rsu_reduce)/'
        'pallas_call"}',
        '  ROOT %_rttg_latency.3 = (f32[8,1]) custom-call(%b), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(_grid)/jit(_rttg_latency)/pallas_call"}',
        '  %fusion.1 = f32[8] fusion(%c), kind=kLoop, metadata={op_name="jit(_grid)/add"}',
    ])
    assert trace.kernel_instructions(hlo) == {"rsu_reduce.8": "rsu_reduce",
                                              "_rttg_latency.3": "_rttg_latency"}


def test_op_name_reads_the_instruction_of_an_event():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") \
        == "fusion.12"
    assert trace.op_name("while.3") == "while.3"


def test_one_chip_and_the_harness_spans(reduced):
    assert [d["name"] for d in reduced["devices"]] == ["/device:TPU:0"]
    assert set(reduced["spans"]) == {"prepare", "run_grid", "block"}
    assert reduced["window_s"] > 0


def test_busy_time_lies_inside_the_window(reduced):
    dev = reduced["devices"][0]
    assert 0 < dev["busy_s"] <= reduced["window_s"]
    idle = sum(s for s, _ in dev["gaps"])
    assert dev["busy_s"] + idle == pytest.approx(reduced["window_s"], rel=1e-9)


def test_every_kernel_of_the_grid_program_is_found(reduced):
    k = reduced["devices"][0]["kernel_s"]
    assert set(k) == {"_rttg_latency", "server_update"}
    assert all(v > 0 for v in k.values())
    # each kernel's events are leaves: their own time is all their time
    ops = reduced["devices"][0]["ops"]
    assert all(ops[w] == pytest.approx(v) for w, v in k.items())


def test_own_times_add_up_to_busy_time_without_double_counting(reduced):
    dev = reduced["devices"][0]
    assert sum(dev["ops"].values()) == pytest.approx(dev["busy_s"], rel=1e-6)


def test_breakdown_keeps_at_most_ten_of_each(reduced):
    b = trace.breakdown(reduced)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
