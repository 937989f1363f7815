"""Logical-axis rules: divisibility fallback, axis-conflict, Param pytree."""
import jax
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container has no hypothesis wheel: deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st
from jax.sharding import AbstractMesh, PartitionSpec

from repro.sharding import (
    Param,
    SERVE_RULES,
    TRAIN_RULES,
    resolve_pspec,
    split_params,
)

MESH1 = AbstractMesh((16, 16), ("data", "model"))
MESH2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_basic_resolution():
    spec = resolve_pspec(("embed", "heads", "head_dim"), (4096, 32, 128), MESH1, TRAIN_RULES)
    assert spec == PartitionSpec("data", "model")


def test_divisibility_fallback():
    spec = resolve_pspec(("embed", "heads", "head_dim"), (768, 12, 64), MESH1, TRAIN_RULES)
    assert spec == PartitionSpec("data")  # 12 heads can't shard 16 ways


def test_pod_axis_only_on_multipod():
    s1 = resolve_pspec(("batch", "seq"), (256, 4096), MESH1, TRAIN_RULES)
    s2 = resolve_pspec(("batch", "seq"), (256, 4096), MESH2, TRAIN_RULES)
    assert s1 == PartitionSpec("data")
    assert s2 == PartitionSpec(("pod", "data"))


def test_batch_one_replicates():
    spec = resolve_pspec(("batch", "seq"), (1, 524288), MESH1, TRAIN_RULES)
    assert spec == PartitionSpec()


def test_expert_mlp_takes_model_when_experts_cannot():
    """Mixtral (8e) vs phi3.5 (16e) on model=16 (DESIGN.md §7)."""
    mix = resolve_pspec((None, "experts", "embed", "expert_mlp"),
                        (32, 8, 4096, 14336), MESH1, TRAIN_RULES)
    assert mix == PartitionSpec(None, None, "data", "model")
    phi = resolve_pspec((None, "experts", "embed", "expert_mlp"),
                        (32, 16, 4096, 6400), MESH1, TRAIN_RULES)
    assert phi == PartitionSpec(None, "model", "data")


def test_serve_rules_keep_params_resident():
    spec = resolve_pspec(("embed", "mlp"), (4096, 14336), MESH1, SERVE_RULES)
    assert spec == PartitionSpec(None, "model")


@settings(max_examples=50, deadline=None)
@given(
    dims=st.lists(st.integers(1, 4096), min_size=1, max_size=4),
    names=st.lists(st.sampled_from(list(TRAIN_RULES) + [None]), min_size=1, max_size=4),
)
def test_resolution_invariants(dims, names):
    n = min(len(dims), len(names))
    dims, names = dims[:n], names[:n]
    spec = resolve_pspec(tuple(names), tuple(dims), MESH2, TRAIN_RULES)
    sizes = dict(MESH2.shape)
    used = []
    for dim, entry in zip(dims, tuple(spec) + (None,) * (n - len(spec))):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        prod = 1
        for a in axes:
            assert a not in used, "mesh axis used twice in one tensor"
            used.append(a)
            prod *= sizes[a]
        assert dim % prod == 0, "uneven partition slipped through"


def test_param_pytree_roundtrip():
    p = {"w": Param(jnp.ones((2, 3)), ("embed", "mlp"))}
    leaves, treedef = jax.tree_util.tree_flatten(p)
    assert len(leaves) == 1
    p2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert p2["w"].axes == ("embed", "mlp")
    vals, axes = split_params(p)
    assert vals["w"].shape == (2, 3)
    assert axes["w"] == ("embed", "mlp")


def test_param_axes_survive_eval_shape():
    def init(key):
        return {"w": Param(jax.random.normal(key, (8, 4)), ("embed", "mlp"))}

    struct = jax.eval_shape(init, jax.random.key(0))
    vals, axes = split_params(struct)
    assert vals["w"].shape == (8, 4)
    assert axes["w"] == ("embed", "mlp")


def test_param_rank_mismatch_raises():
    with pytest.raises(ValueError):
        Param(jnp.ones((2, 3)), ("embed",))
