"""A four-chip cell's run at a size the CPU can hold, on four virtual
devices, with the look for a chip skipped: `correct` holds for the sharded
program as it is, and fails when the lanes of every chip but the first never
reach the host (the exchange between chips left out).  The cell is the
paper's 24-lane grid (bench/workloads/paper-full-grid.json), cut in size and
added here as a later benchmark PR would add a four-chip cell: by an entry
and a limits file."""
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCRIPT = textwrap.dedent("""
    import json, os, shutil, sys
    root, fault = sys.argv[1], sys.argv[2] == "fault"
    sys.path[:0] = [os.path.join(%(repo)r, "src"), %(repo)r]
    shutil.copytree(os.path.join(%(repo)r, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.load(open(os.path.join(%(repo)r, "BENCHMARK.json")))
    spec["configs"].append({"name": "mnist-mlp-small", "reduced": ["num_clients"],
        "why": "test", "source": "https://arxiv.org/abs/2305.11654",
        "file": "bench/configs/mnist-mlp-small.json"})
    spec["workloads"].append({"name": "paper-grid-4chip", "config": "mnist-mlp-small",
        "traffic": "paper-full-grid", "chips": 4, "why": "test"})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    shutil.copy(os.path.join(root, "bench/limits/mnist-paper-grid.json"),
                os.path.join(root, "bench/limits/paper-grid-4chip.json"))
    cfg = json.load(open(os.path.join(root, "bench/configs/mnist-mlp-paper.json")))
    # the paper grid, its 24 lanes sharded 6 a device, 6 rounds, ten clusters
    # of one home region each so that each clustering is firm
    cfg["fl"].update(num_clients=40, samples_per_client=32, batch_size=16,
                     sketch_dim=256, num_clusters=10)
    json.dump(cfg, open(os.path.join(root, "bench/configs/mnist-mlp-small.json"), "w"))
    path = os.path.join(root, "bench/workloads/paper-full-grid.json")
    mix = json.load(open(path))
    mix.update(rounds=6, check_lanes=4, check_rounds=6)
    json.dump(mix, open(path, "w"))
    peaks = json.load(open(os.path.join(root, "bench/peaks.json")))
    peaks["cpu"] = peaks["TPU v5 lite"]
    json.dump(peaks, open(os.path.join(root, "bench/peaks.json"), "w"))

    import jax
    from bench import run
    run.check_device = lambda chips: jax.devices()[:chips]
    run.peak_bytes = lambda devices, rec: 1
    if fault:
        import repro.fl.engine as engine
        build = engine.ExperimentEngine._build_sharded

        def faulty(self, *a, **k):
            fn = build(self, *a, **k)

            def call(*args):
                states, metrics = fn(*args)
                first = jax.tree_util.tree_leaves(metrics)[0].shape[0] // 4
                return states, jax.tree_util.tree_map(
                    lambda x: x.at[first:].set(0), metrics)

            return call

        engine.ExperimentEngine._build_sharded = faulty
    run.main(["--workload", "paper-grid-4chip", "--seed", "31", "--seconds", "1",
              "--trace", "0"], root=root)
""") % {"repo": ROOT}


def result(tmp_path, mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path / "root"), mode],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_sharded_program_as_it_is_is_correct(tmp_path):
    out = result(tmp_path, "sound")
    assert out["device"]["count"] == 4
    assert out["correct"], out["checked"]


def test_lanes_that_never_leave_their_chip_are_caught(tmp_path):
    assert not result(tmp_path, "fault")["correct"]
