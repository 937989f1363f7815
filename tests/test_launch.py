"""Launch layer: input specs, cache specs, trip counts, HLO analysis,
the persistent compile cache's placement."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.config import INPUT_SHAPES, TrainConfig, shape_by_name
from repro.configs import get_smoke_config
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR
from repro.launch.hlo_analysis import parse_hlo, scope_trip_counts
from repro.launch.steps import (
    TrainState,
    cache_specs,
    input_specs,
    make_train_step,
    opt_state_axes,
)
from repro.models import build_model
from repro.sharding import split_params


def test_input_specs_shapes():
    cfg = get_smoke_config("qwen1.5-0.5b")
    specs, axes = input_specs(cfg, shape_by_name("train_4k"))
    assert specs["tokens"].shape == (256, 4096)
    assert specs["targets"].dtype == jnp.int32
    assert axes["tokens"] == ("batch", "seq")

    specs, _ = input_specs(cfg, shape_by_name("decode_32k"))
    assert specs["tokens"].shape == (128,)


def test_input_specs_vlm_splits_image_tokens():
    cfg = get_smoke_config("internvl2-76b")
    specs, _ = input_specs(cfg, shape_by_name("train_4k"))
    assert specs["image_embeds"].shape[1] == cfg.num_image_tokens
    assert specs["tokens"].shape[1] == 4096 - cfg.num_image_tokens


def test_cache_specs_no_allocation():
    cfg = get_smoke_config("mixtral-8x7b")
    api = build_model(cfg)
    struct, axes = cache_specs(api, shape_by_name("decode_32k"))
    # SWA layers cap the cache at the window length
    k = struct["layers"][0]["attn"]["k"]
    assert isinstance(k, jax.ShapeDtypeStruct)
    assert k.shape[2] == min(cfg.sliding_window, 32_768)


def test_scope_trip_counts():
    cfg = get_smoke_config("gemma2-9b")  # pattern period 2
    trips = scope_trip_counts(cfg, shape_by_name("train_4k"))
    assert trips["layer"] == cfg.num_layers // 2
    assert trips["qscan"] == 4096 / min(cfg.attn_block_q, 4096)
    cfgm = get_smoke_config("mamba2-130m")
    trips = scope_trip_counts(cfgm, shape_by_name("prefill_32k"))
    assert trips["ssd_chunk"] == -(-32768 // cfgm.ssm_chunk)


def test_parse_hlo_counts_scan_trips():
    """End-to-end: compile a scanned matmul, check trip-weighted flops."""
    def f(w, x):
        def body(x, wi):
            with jax.named_scope("layer"):
                return jnp.tanh(x @ wi), None
        x, _ = jax.lax.scan(body, x, w)
        return jnp.sum(x)

    w = jnp.zeros((6, 32, 32))
    x = jnp.zeros((8, 32))
    hlo = jax.jit(f).lower(w, x).compile().as_text()
    stats0 = parse_hlo(hlo, {})
    stats6 = parse_hlo(hlo, {"layer": 6.0})
    expect_one = 2 * 8 * 32 * 32
    assert stats0.dot_flops == pytest.approx(expect_one, rel=0.01)
    assert stats6.dot_flops == pytest.approx(6 * expect_one, rel=0.01)


def test_train_step_runs_and_state_axes_align():
    cfg = get_smoke_config("qwen1.5-0.5b")
    api = build_model(cfg)
    params_p = api.init(jax.random.key(0))
    params, axes = split_params(params_p)
    step, opt = make_train_step(api, TrainConfig(optimizer="adamw", learning_rate=1e-3))
    state = TrainState(params, opt.init(params))
    oa = opt_state_axes(axes)
    # axes trees must mirror the state structure
    jax.tree_util.tree_structure(state.opt_state.mu) == jax.tree_util.tree_structure(oa.mu)
    batch = {
        "tokens": jnp.ones((2, 16), jnp.int32),
        "targets": jnp.ones((2, 16), jnp.int32),
    }
    state2, metrics = jax.jit(step)(state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert int(state2.opt_state.step) == 1


def test_fl_sim_unknown_scenario_lists_catalog():
    """Satellite: --scenario with an unknown name errors with the registered
    catalog instead of a raw KeyError (both the CLI and the programmatic
    ``run_experiment`` entry point)."""
    from repro.core.scenarios import SCENARIOS
    from repro.launch import fl_sim

    with pytest.raises(ValueError) as ei:
        fl_sim.run_experiment("mnist", "contextual", rounds=1, scenario="atlantis")
    msg = str(ei.value)
    assert "atlantis" in msg
    for name in SCENARIOS:
        assert name in msg, f"registered scenario {name} missing from the error"


def test_fl_sim_cli_unknown_scenario_exits_with_catalog(capsys):
    from repro.launch import fl_sim

    with pytest.raises(SystemExit) as ei:
        fl_sim.main(["--scenario", "atlantis"])
    assert ei.value.code == 2  # argparse usage error, not a stack trace
    err = capsys.readouterr().err
    assert "atlantis" in err and "registered catalog" in err
    assert "platoon" in err and "day_cycle" in err


def test_fl_sim_unknown_aggregator_lists_catalog():
    """Satellite: --aggregator mirrors --scenario — unknown names error
    with the registered registry (CLI and programmatic entry points)."""
    from repro.fl.aggregators import AGGREGATOR_ORDER
    from repro.launch import fl_sim

    with pytest.raises(ValueError) as ei:
        fl_sim.run_experiment("mnist", "contextual", rounds=1,
                              aggregator="fedsgd")
    msg = str(ei.value)
    assert "fedsgd" in msg
    for name in AGGREGATOR_ORDER:
        assert name in msg, f"registered aggregator {name} missing from the error"


def test_fl_sim_cli_unknown_aggregator_exits_with_catalog(capsys):
    from repro.launch import fl_sim

    with pytest.raises(SystemExit) as ei:
        fl_sim.main(["--aggregator", "fedsgd"])
    assert ei.value.code == 2  # argparse usage error, not a stack trace
    err = capsys.readouterr().err
    assert "fedsgd" in err and "registered catalog" in err
    assert "fedyogi" in err and "stale" in err


def test_fl_sim_unknown_dtype_lists_supported():
    """Satellite: --dtype mirrors the catalog errors — an unknown dtype
    name fails fast naming the supported set (CLI and programmatic entry
    points), before any model/data work."""
    from repro.config import FLConfig
    from repro.launch import fl_sim

    with pytest.raises(ValueError) as ei:
        fl_sim.run_experiment("mnist", "contextual", rounds=1, dtype="fp16")
    msg = str(ei.value)
    assert "fp16" in msg
    for name in FLConfig.SUPPORTED_DTYPES:
        assert name in msg, f"supported dtype {name} missing from the error"


def test_fl_sim_cli_unknown_dtype_exits_with_supported_set(capsys):
    from repro.launch import fl_sim

    with pytest.raises(SystemExit) as ei:
        fl_sim.main(["--dtype", "fp16"])
    assert ei.value.code == 2  # argparse usage error, not a stack trace
    err = capsys.readouterr().err
    assert "fp16" in err and "supported dtypes" in err
    assert "float32" in err and "bfloat16" in err


def test_flconfig_rejects_unknown_dtype_strings():
    """FLConfig.__post_init__ names the supported set for either field."""
    from repro.config import FLConfig

    for field in ("param_dtype", "compute_dtype"):
        with pytest.raises(ValueError) as ei:
            FLConfig(**{field: "float16"})
        msg = str(ei.value)
        assert field in msg and "float16" in msg
        assert "float32" in msg and "bfloat16" in msg
    # the supported set is constructible
    FLConfig(param_dtype="bfloat16", compute_dtype="bfloat16")


def test_production_mesh_axes():
    from repro.launch.mesh import make_production_mesh
    # only shape math here (needs 256 devices to actually build)
    import inspect
    src = inspect.getsource(make_production_mesh)
    assert "(2, 16, 16)" in src and "(16, 16)" in src


def test_all_input_shapes_registered():
    assert set(INPUT_SHAPES) == {"train_4k", "prefill_32k", "decode_32k", "long_500k"}
    s = shape_by_name("long_500k")
    assert s.seq_len == 524_288 and s.global_batch == 1 and s.mode == "decode"


_CACHE_PROBE = (
    "from repro.launch.compile_cache import use_compile_cache\n"
    "print(use_compile_cache())\n"
    "import jax\n"
    "jax.jit(lambda x: x * 3.0 + 1.0)(2.0).block_until_ready()\n"
)


def _run_cache_probe(env_dir):
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu", JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_compile_cache_follows_env_dir(tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the only place entries land."""
    before = _entries(CHECKOUT_CACHE_DIR)
    assert _run_cache_probe(tmp_path) == str(tmp_path)
    assert _entries(tmp_path)
    assert _entries(CHECKOUT_CACHE_DIR) == before


def test_compile_cache_defaults_to_checkout():
    """Unset, the cache is the checkout's gitignored .jax_cache, a path
    that depends on nothing but where this checkout lies."""
    root = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
    assert CHECKOUT_CACHE_DIR == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    assert _run_cache_probe(None) == CHECKOUT_CACHE_DIR
    assert _entries(CHECKOUT_CACHE_DIR)
