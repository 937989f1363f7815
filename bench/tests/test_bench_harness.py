"""The benchmark's data files, its contract, and its refusal to run off the chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_to_the_contract():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert s["paths"] == ["bench"] and s["command"] == ["python3", "bench/run.py"]
    assert 1 <= s["run_seconds"] <= 51
    names = [m["name"] for m in s["end_to_end"] + s["per_layer"]]
    names += [c["name"] for c in s["configs"]] + [w["name"] for w in s["workloads"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in s["end_to_end"]}
    assert "setup_s" in e2e
    for m in s["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in s["end_to_end"] + s["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    cells = {w["name"] for w in s["workloads"]}
    for m in s["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    four = sum(w["chips"] == 4 for w in s["workloads"])
    assert four <= max(1, len(s["workloads"]) // 2)
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


@pytest.mark.parametrize("cell", [w["name"] for w in spec()["workloads"]])
def test_every_cell_loads_and_cross_references(cell):
    c = run.load_cell(cell)
    cfg, mix = c["cfg"], c["mix"]
    assert c["limits"] and set(c["limits"]) <= {
        "geometry_gap", "count_gap", "loss_gap"}
    for key in ("model", "dataset", "shapes", "fl", "assumed", "reduced"):
        assert key in cfg
    assert cfg["reduced"] == c["config_spec"]["reduced"]
    for key in ("strategies", "aggregators", "scenarios", "rounds", "eval_every",
                "warmup", "check_lanes", "check_rounds"):
        assert key in mix
    assert set(mix["scenarios"]) <= set(run.reference.SCENARIOS)
    assert run.lanes_of(mix) % c["chips"] == 0
    for m in c["per_layer"]:
        assert callable(run.metric_reader(m["name"]))


def test_every_config_is_used_and_every_fl_field_is_stated():
    from repro.config import FLConfig
    import dataclasses

    s = spec()
    used = {w["config"] for w in s["workloads"]}
    fields = {f.name for f in dataclasses.fields(FLConfig)}
    for c in s["configs"]:
        assert c["name"] in used
        with open(os.path.join(ROOT, c["file"])) as f:
            assert set(json.load(f)["fl"]) == fields


def _copy_tree(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return root


def test_an_added_file_becomes_a_cell_or_a_metric(tmp_path):
    root = _copy_tree(tmp_path)
    s = spec()
    mix = dict(run.load_json(os.path.join(ROOT, "bench/workloads/paper-full-grid.json")),
               scenarios=["ring"])
    (root / "bench/workloads/paper-ring.json").write_text(json.dumps(mix))
    (root / "bench/limits/paper-ring.json").write_text(json.dumps(
        run.load_json(os.path.join(ROOT, "bench/limits/mnist-paper-grid.json"))))
    (root / "bench/metrics/sweeps_traced.py").write_text(
        "def read(ctx):\n    return float(ctx['sweeps'])\n")
    s["workloads"].append({"name": "paper-ring", "config": "mnist-mlp-paper",
                           "traffic": "paper-ring", "chips": 1, "why": "a test"})
    s["per_layer"].append({"name": "sweeps_traced", "unit": "sweeps", "better": "higher",
                           "source": "host_clock", "layer": "engine",
                           "moves": "lane_rounds_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    cell = run.load_cell("paper-ring", str(root))
    assert cell["mix"]["scenarios"] == ["ring"]
    assert "sweeps_traced" in [m["name"] for m in cell["per_layer"]]
    assert run.metric_reader("sweeps_traced", str(root))({"sweeps": 2}) == 2.0


def _run(args, cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_a_run_off_the_chip_fails_and_prints_no_result(tmp_path):
    p = _run(["--workload", "mnist-paper-grid", "--seed", str(2 ** 31 + 5), "--seconds", "1",
              "--trace", "0"], ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout


def test_sweep_seeds_are_fixed_and_fit_a_signed_int():
    seeds = [run.sweep_seed(2 ** 31 + 17, i) for i in range(-1, 50)]
    assert seeds == [run.sweep_seed(2 ** 31 + 17, i) for i in range(-1, 50)]
    assert len(set(seeds)) == len(seeds) and all(0 <= s < 2 ** 31 for s in seeds)
