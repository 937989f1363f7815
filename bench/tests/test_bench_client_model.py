"""A client model of another family comes in as new files alone.

A checkout of the benchmark gains a module under bench/models/ whose data
are not images (integer token rows per vehicle, a next-token softmax over
a small vocabulary, a frozen embedding that no vehicle sends), a
configuration that names it, a traffic mix, limits and a cell, with no
file it already had edited: the harness resolves the module, the lane
reference runs it, the FLOP count and the shape check are its own.  And a
copy of the image classifier under another name runs a whole cell."""
import contextlib
import io
import json
import math
import os
import shutil
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import check, flops, reference, run  # noqa: E402

NEXT_TOKEN = '''"""Next-token prediction over walks through a small vocabulary: a
frozen random embedding (the base, which no vehicle sends), then a
trainable residual adapter and head (the payload), under a softmax."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import fold_in_str

N_TEST = 256
FAMILIES = ("ssm", "hybrid", "dense")


def check_shapes(model, shapes):
    if model.family not in FAMILIES or shapes["vocab"] > model.vocab_size:
        raise ValueError(f"{model.name} ({model.family}, vocab {model.vocab_size}) is "
                         f"no sequence model of vocab {shapes['vocab']}")


def _base(shapes):
    return jax.random.normal(jax.random.key(7), (shapes["vocab"], shapes["d_model"]))


def init(key, shapes):
    d, v = shapes["d_model"], shapes["vocab"]
    return [jnp.zeros((d, d)), 0.01 * jax.random.normal(key, (d, v)), jnp.zeros((v,))]


def loss(leaves, x, y, shapes, dtype):
    r = lambda a: a.astype(jnp.dtype(dtype))
    adapter, w, b = [r(a) for a in leaves]
    h = r(_base(shapes))[x]
    h = h + jnp.tanh(h @ adapter)
    logits = (h @ w + b).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
    return jnp.mean(logz - gold), acc


def null_loss(shapes):
    return float(np.log(shapes["vocab"]))


def _walks(key, strides, count, v, t):
    start = jax.random.randint(key, (count, 1), 0, v)
    seq = (start + strides * jnp.arange(t + 1)[None]) % v
    return seq[:, :-1], seq[:, 1:]


def client_data(key, dataset, shapes, regions, n, classes_per_client):
    """Each vehicle walks the vocabulary by its home region's stride."""
    v, t = shapes["vocab"], shapes["seq_len"]
    kd = fold_in_str(key, f"data/{dataset}")
    keys = jax.random.split(fold_in_str(kd, "rows"), regions.shape[0])
    x, y = jax.vmap(lambda k, r: _walks(k, 1 + r, n, v, t))(keys, regions)
    strides = 1 + jax.random.randint(fold_in_str(kd, "test-strides"), (N_TEST, 1), 0, 10)
    test_x, test_y = _walks(fold_in_str(kd, "test"), strides, N_TEST, v, t)
    return x, y, test_x, test_y


def train_flops(shapes):
    d, v, t = shapes["d_model"], shapes["vocab"], shapes["seq_len"]
    return t * (2 * d * d + 2 * d * v)


eval_flops = train_flops


def eval_samples(shapes):
    return N_TEST
'''

SHAPES = {"vocab": 32, "seq_len": 8, "d_model": 16, "params": 16 * 16 + 16 * 32 + 32}
SMALL_FL = dict(samples_per_client=32, batch_size=16, sketch_dim=256)


def _copy_tree(root):
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")


def _add(root, config, cell, cfg, mix):
    """A configuration, its cell, the cell's traffic and limits: new files,
    and entries at the end of BENCHMARK.json's lists."""
    (root / "bench/configs" / f"{config}.json").write_text(json.dumps(cfg))
    (root / "bench/workloads" / f"{cell}.json").write_text(json.dumps(mix))
    shutil.copy(root / "bench/limits/mnist-paper-grid.json", root / "bench/limits" / f"{cell}.json")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": config, "source": "https://arxiv.org/abs/2305.11654",
                            "file": f"bench/configs/{config}.json",
                            "reduced": cfg["reduced"], "why": "a test"})
    spec["workloads"].append({"name": cell, "config": config, "traffic": cell, "chips": 1,
                              "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def _paper_cfg():
    return run.load_json(os.path.join(ROOT, "bench/configs/mnist-mlp-paper.json"))


def _paper_mix():
    return run.load_json(os.path.join(ROOT, "bench/workloads/paper-full-grid.json"))


@pytest.fixture(scope="module")
def tokens_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    _copy_tree(root)
    (root / "bench/models/next_token.py").write_text(NEXT_TOKEN)
    cfg = dict(_paper_cfg(), model="mamba2-130m", dataset="walks", client_model="next_token",
               shapes=SHAPES, reduced=["num_clients"], assumed=["a test"])
    cfg["fl"] = dict(cfg["fl"], num_clients=20, num_clusters=4, **SMALL_FL)
    mix = dict(_paper_mix(), strategies=["contextual", "network"],
               aggregators=["fedavg", "fedbuff"], scenarios=["ring"], rounds=3,
               eval_every=1, check_lanes=1, check_rounds=3)
    _add(root, "walks-tiny", "walks-grid", cfg, mix)
    return root


def test_a_new_client_model_needs_no_edit_of_a_file_the_benchmark_has(tokens_root):
    for dirpath, dirnames, files in os.walk(os.path.join(ROOT, "bench")):
        dirnames[:] = [d for d in dirnames if d not in ("__pycache__", "tests")]
        for name in files:
            path = os.path.join(dirpath, name)
            with open(path, "rb") as a, open(tokens_root / os.path.relpath(path, ROOT),
                                              "rb") as b:
                assert a.read() == b.read(), path
    old, new = run.load_json(os.path.join(ROOT, "BENCHMARK.json")), json.loads(
        (tokens_root / "BENCHMARK.json").read_text())
    for key, value in old.items():
        if isinstance(value, list):
            assert new[key][:len(value)] == value, key
        else:
            assert new[key] == value, key


def test_the_harness_resolves_the_module_the_configuration_names(tokens_root):
    cell = run.load_cell("walks-grid", str(tokens_root))
    assert cell["model_file"] == str(tokens_root / "bench/models/next_token.py")
    assert cell["model"].null_loss(cell["cfg"]["shapes"]) == math.log(32)


@pytest.mark.parametrize("family, refusal", [(None, "names no client model"),
                                              ("../configs/x", "names no client model"),
                                              ("absent", "no client model bench/models/absent")])
def test_a_configuration_must_name_a_module_that_is_there(tmp_path, family, refusal):
    root = tmp_path / "checkout"
    _copy_tree(root)
    cfg = dict(_paper_cfg(), client_model=family)
    _add(root, "mnist-mlp-nameless", "nameless-grid", cfg, _paper_mix())
    with pytest.raises(SystemExit, match=refusal):
        run.load_cell("nameless-grid", str(root))


def test_the_shape_check_is_the_modules(tokens_root):
    cell = run.load_cell("walks-grid", str(tokens_root))
    assert run.model_config(cell).vocab_size == 50280
    image = dict(cell, cfg=dict(cell["cfg"], model="fl-mnist-mlp"))
    with pytest.raises(SystemExit, match="no sequence model of vocab 32"):
        run.model_config(image)


def test_the_lane_reference_runs_the_module(tokens_root):
    cell = run.load_cell("walks-grid", str(tokens_root))
    lane = check.lane_of(cell, ("contextual", "fedbuff", 0, "ring"))
    got = reference.run_lane(lane, 2 ** 31 + 7)
    loss = np.asarray(got.test_loss)
    assert loss.shape == (3,) and np.all(np.isfinite(loss)), loss
    assert abs(loss[0] - math.log(32)) < 0.05 * math.log(32), loss
    assert np.all(np.asarray(got.n_selected) == 2)


def test_the_flop_count_is_the_modules(tokens_root):
    cell = run.load_cell("walks-grid", str(tokens_root))
    fwd = 8 * (2 * 16 * 16 + 2 * 16 * 32)  # per row of 8 tokens: adapter and head
    train = 3 * 2 * 2 * 16 * 3 * fwd  # 3 rounds x 2 clients x 2 steps of 16 rows
    warm = 20 * 16 * 3 * fwd  # one batch on every vehicle
    ev = 3 * 256 * fwd  # an eval every round
    assert flops.lane_model_flops(cell["model"], cell["cfg"]["shapes"], cell["cfg"]["fl"],
                                  cell["mix"]) == train + warm + ev


def test_a_copy_of_the_image_classifier_runs_a_whole_cell(tmp_path, monkeypatch):
    """The copy under another name, its configuration and its cell, cut to
    a size the CPU holds as bench/tests/test_bench_faults.py cuts it."""
    root = tmp_path / "checkout"
    _copy_tree(root)
    shutil.copy(root / "bench/models/image_classifier.py", root / "bench/models/classifier2.py")
    cfg = dict(_paper_cfg(), client_model="classifier2", reduced=["num_clients"])
    cfg["fl"] = dict(cfg["fl"], num_clients=40, num_clusters=10, **SMALL_FL)
    mix = dict(_paper_mix(), rounds=6, strategies=["contextual", "network"],
               aggregators=["fedavg", "fedadam"], scenarios=["rush_hour"], check_lanes=2,
               check_rounds=6)
    _add(root, "mnist-mlp-classifier2", "classifier2-grid", cfg, mix)

    load = run.load_json
    with_cpu = lambda p: dict(load(p), cpu=load(p)["TPU v5 lite"])
    monkeypatch.setattr(run, "load_json",
                        lambda p: with_cpu(p) if p.endswith("peaks.json") else load(p))
    monkeypatch.setattr(run, "check_device", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "peak_bytes", lambda devices, rec: 1)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", "classifier2-grid", "--seed", str(2 ** 31 + 41),
                  "--seconds", "0.5", "--trace", "0"], root=str(root))
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"], result["checked"]
    assert result["failed"] == 0
