"""shard_map expert-parallel MoE == GSPMD reference, on 8 fake devices.

Runs in a subprocess because --xla_force_host_platform_device_count must be
set before jax initializes (the main pytest process keeps 1 device so smoke
tests see the normal environment).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.config import ModelConfig
    from repro.models.moe import init_moe, _moe_gspmd, _moe_shard_map
    from repro.sharding import split_params

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    for E in (4, 2):  # expert-sharded and ff-sliced cases
        cfg = ModelConfig(name="m", family="moe", num_layers=1, d_model=64,
                          num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
                          num_experts=E, experts_per_token=2, dtype="float32")
        params, _ = split_params(init_moe(jax.random.key(0), cfg, 1, jnp.float32))
        p0 = jax.tree_util.tree_map(lambda a: a[0], params)
        x = jax.random.normal(jax.random.key(1), (8, 16, 64))
        y_ref, _ = _moe_gspmd(p0, x, cfg)
        with mesh:
            y_sm, _ = jax.jit(lambda p, x: _moe_shard_map(p, x, cfg, mesh))(p0, x)
        diff = float(jnp.max(jnp.abs(y_ref - y_sm)))
        assert diff < 1e-5, f"E={E}: shard_map diverges from reference: {diff}"
        # gradients flow through the shard_map path
        g = jax.grad(lambda p: jnp.sum(
            jax.jit(lambda pp, xx: _moe_shard_map(pp, xx, cfg, mesh))(p, x)[0] ** 2
        ))(p0)
        gn = sum(float(jnp.abs(t).sum()) for t in jax.tree_util.tree_leaves(g))
        assert gn > 0, f"E={E}: zero grads through shard_map"
    print("MOE_DISTRIBUTED_OK")
""")


@pytest.mark.slow
def test_shard_map_moe_matches_reference_on_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True, text=True,
        timeout=500,
    )
    assert "MOE_DISTRIBUTED_OK" in out.stdout, out.stderr[-2000:]
