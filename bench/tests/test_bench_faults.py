"""A whole run of the `mnist-paper-grid` cell at a size the CPU can hold,
with the look for a chip skipped: `correct` holds for the program as it is
and fails for each fault planted in the timed path underneath (a round that
returns its state unchanged, half the cohort left out of the mean, an
answer altered where it is produced), and for the control."""
import contextlib
import io
import json
import os
import shutil
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import calibrate, run  # noqa: E402

CELL = "mnist-paper-grid"
N = 40  # vehicles, a cohort of 4


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose cell is cut to ``N`` vehicles with 32 samples each,
    4 of its lanes and 6 rounds (a recluster after the 5th)."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    cfg_path = root / "bench/configs/mnist-mlp-paper.json"
    cfg = json.loads(cfg_path.read_text())
    # ten clusters, one a home region, so that each clustering is firm
    cfg["fl"].update(num_clients=N, samples_per_client=32, batch_size=16,
                     sketch_dim=256, num_clusters=10)
    cfg_path.write_text(json.dumps(cfg))
    mix_path = root / "bench/workloads/paper-full-grid.json"
    mix = json.loads(mix_path.read_text())
    mix.update(rounds=6, strategies=["contextual", "network"],
               aggregators=["fedavg", "fedadam"], scenarios=["rush_hour"],
               check_lanes=2, check_rounds=6)
    mix_path.write_text(json.dumps(mix))
    peaks = json.loads((root / "bench/peaks.json").read_text())
    peaks["cpu"] = peaks["TPU v5 lite"]
    (root / "bench/peaks.json").write_text(json.dumps(peaks))
    return str(root)


@pytest.fixture
def off_chip(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "check_device", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "peak_bytes", lambda devices, rec: 1)
    # a cache directory of the environment's is left to JAX, which read the
    # environment at import: the CPU's programs are not cached anywhere
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def result(root):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", CELL, "--seed", str(2 ** 31 + 99), "--seconds", "0.5",
                  "--trace", "0"], root=root)
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _wrap_round_step(monkeypatch, fault):
    import repro.fl.engine as engine

    make = engine.make_round_step

    def make_faulty(*a, **k):
        step = make(*a, **k)
        return lambda state, *sa, **sk: fault(state, *step(state, *sa, **sk))

    monkeypatch.setattr(engine, "make_round_step", make_faulty)


def test_the_program_as_it_is_is_correct(root, off_chip):
    out = result(root)
    assert out["correct"], out["checked"]
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_a_round_that_returns_its_state_unchanged_is_caught(root, off_chip, monkeypatch):
    _wrap_round_step(monkeypatch, lambda old, new, metrics: (old, metrics))
    assert not result(root)["correct"]


def test_half_the_cohort_left_out_of_the_mean_is_caught(root, off_chip, monkeypatch):
    import jax.numpy as jnp
    import repro.fl.rounds as rounds

    weights = rounds.normalized_weights

    def half(mask, counts):
        return weights(mask & (jnp.arange(mask.shape[0]) % 2 == 0), counts)

    monkeypatch.setattr(rounds, "normalized_weights", half)
    assert not result(root)["correct"]


def test_an_answer_altered_where_it_is_produced_is_caught(root, off_chip, monkeypatch):
    _wrap_round_step(monkeypatch, lambda old, new, m: (
        new, m._replace(mean_real_latency=m.mean_real_latency * 1.01)))
    assert not result(root)["correct"]


def test_the_control_fails_and_the_program_passes(root, off_chip):
    cell = run.load_cell(CELL, root)
    out = calibrate.readings(cell, jax.devices()[:1], seeds=1, base_seed=7,
                             control=True, faults=())
    limits = cell["limits"]
    for row in out["program"]:
        assert all(row[k] <= limits[k] for k in limits), row
    # the program's own `high` path computes as `highest` does on the CPU,
    # so here the control is the reference one precision down
    for row in out["control_reference"]:
        assert any(row[k] > limits[k] for k in limits), row
