"""FL runtime: partitioning, local training, FedAvg, round accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container has no hypothesis wheel: deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.config import FLConfig, ModelConfig, TrafficConfig
from repro.fl.client import make_local_trainer
from repro.fl.partition import make_test_set, partition_clients
from repro.fl.server import fedavg_aggregate, normalized_weights
from repro.models import build_model
from repro.sharding import split_params
from repro.utils import tree_weighted_sum

MLP = ModelConfig(name="mlp", family="mlp", num_layers=0, d_model=0, num_heads=0,
                  num_kv_heads=0, d_ff=64, vocab_size=0, image_shape=(28, 28, 1),
                  num_classes=10, channels=())
CNN = ModelConfig(name="cnn", family="cnn", num_layers=0, d_model=0, num_heads=0,
                  num_kv_heads=0, d_ff=16, vocab_size=0, image_shape=(32, 32, 3),
                  num_classes=10, channels=(4,))


def test_partition_classes_per_client():
    fl = FLConfig(num_clients=20, samples_per_client=64, classes_per_client=2)
    images, labels = partition_clients(jax.random.key(0), "mnist", fl)
    assert images.shape == (20, 64, 28 * 28 * 1)
    l = np.asarray(labels)
    for c in range(20):
        assert len(set(l[c].tolist())) <= 2


def test_partition_iid_when_full_ratio():
    fl = FLConfig(num_clients=10, samples_per_client=256, classes_per_client=10)
    _, labels = partition_clients(jax.random.key(0), "mnist", fl)
    # most clients should see most classes
    counts = [len(set(np.asarray(labels)[c].tolist())) for c in range(10)]
    assert np.mean(counts) > 8


def test_partition_dirichlet():
    fl = FLConfig(num_clients=10, samples_per_client=128, dirichlet_alpha=0.3)
    images, labels = partition_clients(jax.random.key(0), "cifar10", fl)
    assert images.shape == (10, 128, 32 * 32 * 3)
    assert int(labels.max()) < 10


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), k=st.integers(1, 8))
def test_fedavg_is_weighted_mean(seed, k):
    ks = jax.random.split(jax.random.key(seed), 3)
    base = {"w": jax.random.normal(ks[0], (4, 3)), "b": jax.random.normal(ks[1], (3,))}
    ups = jax.tree_util.tree_map(
        lambda x: jax.random.normal(ks[2], (k,) + x.shape), base
    )
    w = jnp.ones((k,)) / k
    out = fedavg_aggregate(base, ups, w)
    expect = jax.tree_util.tree_map(
        lambda p, u: p + jnp.mean(u, axis=0), base, ups
    )
    for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(expect)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_normalized_weights_mask_and_sum():
    mask = jnp.array([True, False, True, True])
    n = jnp.array([100, 100, 200, 100])
    w = normalized_weights(mask, n)
    assert float(w[1]) == 0.0
    assert abs(float(w.sum()) - 1.0) < 1e-6
    assert abs(float(w[2]) - 0.5) < 1e-6


def test_local_training_reduces_loss():
    api = build_model(MLP)
    params, _ = split_params(api.init(jax.random.key(0)))
    fl = FLConfig(num_clients=4, samples_per_client=128, classes_per_client=2)
    images, labels = partition_clients(jax.random.key(1), "mnist", fl)
    trainer = make_local_trainer(api.loss, lr=0.05, epochs=2, batch_size=32)
    updates, vecs = trainer(params, images, labels, jax.random.key(2))
    assert vecs.shape[0] == 4
    # apply client 0's update alone: its local loss must drop
    p0 = jax.tree_util.tree_map(lambda p, u: p + u[0], params, updates)
    b = {"images": images[0], "labels": labels[0]}
    l_before = float(api.loss(params, b)[0])
    l_after = float(api.loss(p0, b)[0])
    assert l_after < l_before


def test_update_vectors_match_updates():
    from repro.utils import flatten_to_vector

    api = build_model(MLP)
    params, _ = split_params(api.init(jax.random.key(0)))
    fl = FLConfig(num_clients=2, samples_per_client=64)
    images, labels = partition_clients(jax.random.key(1), "mnist", fl)
    trainer = make_local_trainer(api.loss, lr=0.05, epochs=1, batch_size=32)
    updates, vecs = trainer(params, images, labels, jax.random.key(2))
    u0 = jax.tree_util.tree_map(lambda u: u[0], updates)
    v0, _ = flatten_to_vector(u0)
    np.testing.assert_allclose(np.asarray(vecs[0]), np.asarray(v0), atol=1e-6)


def test_test_set_shares_prototypes_with_clients():
    """A model that learns client data must transfer to the test set."""
    x, y = make_test_set(jax.random.key(0), "mnist", 100)
    assert x.shape == (100, 28, 28, 1)
    x2, y2 = make_test_set(jax.random.key(0), "mnist", 100)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x2))


def _cohort_composition_trainer(loss_fn, lr, batch_size):
    """The cohort trainer as a plain composition: each slot's whole shard
    as (n, H, W, C) images, then each step's batch ``shard[perm]``."""
    from repro.utils import flatten_to_vector

    def local_sgd(p0, images, labels, key):
        n = images.shape[0]
        spe = max(n // batch_size, 1)
        perm = jax.random.permutation(jax.random.split(key, 1)[0], n)
        idx = perm[: spe * batch_size].reshape(spe, batch_size)

        def step(p, bidx):
            batch = {"images": images[bidx], "labels": labels[bidx]}
            g = jax.grad(lambda pp: loss_fn(pp, batch)[0])(p)
            return jax.tree_util.tree_map(lambda w, gw: w - lr * gw, p, g), None

        return jax.lax.scan(step, p0, idx)[0]

    @jax.jit
    def train(params, images, labels, key):
        new = jax.vmap(lambda im, lb, k: local_sgd(params, im, lb, k))(
            images, labels, jax.random.split(key, images.shape[0]))
        return jax.vmap(lambda i: flatten_to_vector(jax.tree_util.tree_map(
            lambda a, b: a[i] - b, new, params))[0])(jnp.arange(images.shape[0]))

    return train


@pytest.mark.parametrize("padded", [False, True], ids=["full", "padded"])
@pytest.mark.parametrize("stacked", [False, True], ids=["store", "stacked"])
@pytest.mark.parametrize("dataset,model", [("mnist", MLP), ("cifar10", CNN)],
                         ids=["mnist", "cifar10"])
def test_row_gather_trainer_matches_cohort_composition(dataset, model, stacked,
                                                       padded):
    """Each step's batch gathered as whole (D,) rows straight from the
    store by (data row, cohort client, permuted sample) trains bitwise the
    same update vectors as ``images[data_idx, idx_c][k][perm]`` on (..., H,
    W, C) images, padding slots zeroed in both."""
    api = build_model(model)
    params, _ = split_params(api.init(jax.random.key(0)))
    fl = FLConfig(num_clients=5, samples_per_client=32)
    shards = [partition_clients(jax.random.key(s), dataset, fl) for s in (1, 2)]
    images = jnp.stack([x for x, _ in shards])  # (rows, C, n, D)
    labels = jnp.stack([y for _, y in shards])
    assert images.shape[-1] == int(np.prod(model.image_shape))
    idx_c = jnp.array([4, 1, 0, 0])
    valid = jnp.array([True, True, not padded, not padded])
    key = jax.random.key(3)
    hwc = images.reshape(images.shape[:-1] + model.image_shape)
    if stacked:
        data_idx = jnp.int32(1)
        rows = (data_idx, idx_c)
        cohort_x, cohort_y = hwc[data_idx, idx_c], labels[data_idx, idx_c]
    else:
        images, labels = images[0], labels[0]
        rows = (idx_c,)
        cohort_x, cohort_y = hwc[0][idx_c], labels[idx_c]

    trainer = make_local_trainer(api.loss, lr=0.05, epochs=1, batch_size=8)
    _, vecs = trainer(params, images, labels, key, rows=rows, valid=valid)

    cohort_x = cohort_x * valid.reshape(-1, 1, 1, 1, 1)
    cohort_y = jnp.where(valid[:, None], cohort_y, 0)
    want = _cohort_composition_trainer(api.loss, 0.05, 8)(
        params, cohort_x, cohort_y, key)
    np.testing.assert_array_equal(np.asarray(vecs), np.asarray(want))
