"""The program's own tracing (``repro.utils.tracing``) on the CPU.

The grid program's ops carry an ``fl.<stage>`` scope for every stage, nested
in the scan body's ``round`` scope so ``hlo_analysis``'s trip-weighting holds;
the scopes are metadata only; a second sweep of one shape compiles nothing;
``run_grid``'s ``engine.`` spans land in a profiler session and nowhere else.
"""
import contextlib
import os
import re
import sys
import tempfile

import jax
import pytest

from repro.config import FLConfig, ModelConfig
from repro.fl import engine as engine_mod
from repro.fl import rounds as rounds_mod
from repro.fl.engine import ExperimentEngine
from repro.launch.hlo_analysis import parse_hlo
from repro.utils import tracing

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from bench.stage_trace import host_spans, load, strip_metadata  # noqa: E402
from bench.trace import find_xplane  # noqa: E402

MLP = ModelConfig(name="mlp", family="mlp", num_layers=0, d_model=0, num_heads=0,
                  num_kv_heads=0, d_ff=16, vocab_size=0, image_shape=(28, 28, 1),
                  num_classes=10, channels=())
FL = FLConfig(num_clients=8, samples_per_client=16, local_epochs=1, num_clusters=2,
              batch_size=8, recluster_every=2, sketch_dim=64)
ROUNDS = 2
RUN = dict(scenarios=("ring",), rounds=ROUNDS, eval_every=ROUNDS)
ROUND_STAGES = ("geometry", "select", "train", "server", "eval")


def _engine():
    return ExperimentEngine(MLP, FL, "mnist", strategies=("contextual", "gossip"),
                            aggregators=("fedavg", "fedbuff"), warmup=True)


def _compiled_text(eng) -> str:
    """The compiled grid program of one sweep of ``RUN``, by its shapes."""
    call = {}
    fn = eng._grid_fn

    def capture(*args, **kwargs):
        call["args"] = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        call["kwargs"] = kwargs
        return fn(*args, **kwargs)

    eng._grid_fn = capture
    jax.block_until_ready(eng.run_grid(seeds=(0,), **RUN).metrics)
    eng._grid_fn = fn
    return fn.lower(*call["args"], **call["kwargs"]).compile().as_text()


@pytest.fixture(scope="module")
def eng():
    return _engine()


@pytest.fixture(scope="module")
def hlo(eng):
    return _compiled_text(eng)


def _op_names(hlo_text):
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def test_every_stage_scope_reaches_the_compiled_grid_program(hlo):
    found = {m for op in _op_names(hlo) for m in re.findall(r"fl\.(\w+)", op)}
    assert found == set(tracing.STAGES)


def test_round_stages_nest_in_the_round_scope(hlo):
    """The per-round stages sit under the ``round`` scope (trip-weighted by
    hlo_analysis); init and the warm-up lie outside it."""
    for op in _op_names(hlo):
        stage = re.search(r"(?:^|[/(])fl\.(\w+)", op)
        if stage is None:
            continue
        inside = "round" in op[:stage.start()].split("/")
        assert inside == (stage.group(1) in ROUND_STAGES), op


def test_stage_scopes_add_no_op(hlo, monkeypatch):
    """Without the scopes the compiled program is the same op for op, and
    the ``round`` trip-weighted counts of hlo_analysis are unchanged."""
    plain = lambda name: contextlib.nullcontext()
    monkeypatch.setattr(rounds_mod, "stage", plain)
    monkeypatch.setattr(engine_mod, "stage", plain)
    bare = _compiled_text(_engine())
    assert "fl.train" not in bare
    assert strip_metadata(bare) == strip_metadata(hlo)
    trips = {"round": float(ROUNDS)}
    a, b = parse_hlo(hlo, trips), parse_hlo(bare, trips)
    assert a.dot_flops > 0
    assert (a.dot_flops, a.hbm_bytes) == (b.dot_flops, b.hbm_bytes)


def test_a_second_sweep_of_one_shape_compiles_nothing(eng, hlo):
    before = tracing.compile_counts()
    assert before["compile"]["count"] > 0  # the first sweep's compile counted
    jax.block_until_ready(eng.run_grid(seeds=(1,), **RUN).metrics)
    assert tracing.compile_counts() == before


def test_stage_names_are_checked():
    with pytest.raises(ValueError, match="unknown stage"):
        tracing.stage("training")


class _Unprintable:
    def __str__(self):
        raise AssertionError("a span argument was formatted outside a session")

    __repr__ = __str__


def test_span_outside_a_profiler_session_does_nothing():
    with tracing.span("outside", arg=_Unprintable()):
        pass
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            with tracing.span("inside", lanes=2):
                pass
        names = [name for _, _, name in host_spans(load(find_xplane(tmp)))]
    assert names == ["engine.inside"]


def test_run_grid_spans_lie_in_the_harness_span(eng, hlo):
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            with jax.profiler.TraceAnnotation("bench.run_grid"):
                res = eng.run_grid(seeds=(2,), **RUN)
            jax.block_until_ready(res.metrics)
        spans = host_spans(load(find_xplane(tmp)))
    [(s0, e0)] = [(s, e) for s, e, n in spans if n == "bench.run_grid"]
    inner = [(s, e, n) for s, e, n in spans if n.startswith("engine.")]
    assert [n for _, _, n in inner] == ["engine.lanes", "engine.stack", "engine.launch"]
    assert all(s0 <= s < e <= e0 for s, e, _ in inner)
    assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))


def test_compile_seconds_count_nested_events_once():
    count = tracing._CompileCount()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    for start, end in [(1.0, 2.0), (3.0, 4.0), (0.5, 10.0), (20.0, 21.0)]:
        count(trace, start, end, fun_name="f")
    count("/jax/some/other_event", 0.0, 100.0)
    snap = count.snapshot()
    assert snap["trace"] == {"count": 4, "seconds": 10.5}
    assert snap["lower"] == snap["compile"] == {"count": 0, "seconds": 0.0}
