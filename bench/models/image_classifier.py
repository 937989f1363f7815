"""The image classifier the paper's vehicles train (`fl-mnist-mlp`,
`fl-cifar10-cnn`): 3x3 SAME convolutions, each with a ReLU and a 2x2 max
pool, then a dense ReLU layer of `d_ff` and a dense layer to
`num_classes` logits, under a softmax cross-entropy.

Its data are synthetic images: one prototype per class plus Gaussian
noise; each vehicle holds `classes_per_client` classes picked by its home
road region.  Shapes: `image_shape` (H, W, C), `channels` (one entry a
convolution), `d_ff`, `num_classes`.  The interface is `bench/models`'.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import fold_in_str

# Synthetic image datasets: (H, W, C), sample noise std, prototype scale.
DATASETS = {
    "mnist": ((28, 28, 1), 0.85, 1.0),
    "cifar10": ((32, 32, 3), 1.60, 1.0),
}
N_TEST = 2_000  # the global test set every eval round classifies


def check_shapes(model, shapes):
    got = (list(model.image_shape), list(model.channels), model.d_ff, model.num_classes)
    want = (list(shapes["image_shape"]), list(shapes["channels"]), shapes["d_ff"],
            shapes["num_classes"])
    if got != want:
        raise ValueError(f"{model.name} has (image_shape, channels, d_ff, num_classes) "
                         f"{got}, the configuration states {want}")


def init(key, shapes):
    """Truncated-normal fan-in init; returns the leaves in flat order."""
    H, W, C = shapes["image_shape"]
    channels, d_ff, classes = shapes["channels"], shapes["d_ff"], shapes["num_classes"]
    ks = jax.random.split(key, 2 + 2 * max(len(channels), 1))
    tn = lambda k, shape, fan: (1.0 / np.sqrt(fan)) * jax.random.truncated_normal(
        k, -2.0, 2.0, shape, jnp.float32)
    leaves, in_c, h, w = [], C, H, W
    for i, out_c in enumerate(channels):
        leaves += [jnp.zeros((out_c,)), tn(ks[i], (3, 3, in_c, out_c), 9 * in_c)]
        in_c, h, w = out_c, h // 2, w // 2
    flat = h * w * in_c
    leaves += [jnp.zeros((d_ff,)), tn(ks[-2], (flat, d_ff), flat)]
    leaves += [jnp.zeros((classes,)), tn(ks[-1], (d_ff, classes), d_ff)]
    return leaves


def _rounder(dtype: str):
    """Value rounding of the model's forward pass in ``dtype``."""
    if dtype == "float32":
        return lambda x: x.astype(jnp.float32)
    if dtype == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16)
    raise ValueError(f"unknown model dtype {dtype!r}")


def logits_fn(leaves, images, n_convs, dtype):
    r = _rounder(dtype)
    x = r(images)
    p = [r(a) for a in leaves]
    for i in range(n_convs):
        b, w = p[2 * i], p[2 * i + 1]
        x = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = r(jax.nn.relu(r(x) + b))
        x = jax.lax.reduce_window(x, np.array(-np.inf, x.dtype), jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    b1, w1, b2, w2 = p[-4:]
    x = r(jax.nn.relu(r(x @ w1) + b1))
    return (r(x @ w2) + b2).astype(jnp.float32)


def loss(leaves, images, labels, shapes, dtype):
    logits = logits_fn(leaves, images, len(shapes["channels"]), dtype)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return jnp.mean(logz - gold), acc


def null_loss(shapes) -> float:
    return float(np.log(shapes["num_classes"]))


def client_data(key, dataset, shapes, regions, n, classes_per_client):
    (H, W, C), noise, scale = DATASETS[dataset]
    classes = shapes["num_classes"]
    kd = fold_in_str(key, f"data/{dataset}")
    protos = scale * jax.random.normal(fold_in_str(kd, f"proto/{dataset}"),
                                       (classes, H, W, C))
    k = classes_per_client
    own = jnp.mod(regions[:, None] + jnp.arange(k)[None, :], classes)
    kl = jax.random.split(fold_in_str(kd, "labels"), regions.shape[0])
    pick = jax.vmap(lambda kk: jax.random.randint(kk, (n,), 0, k))(kl)
    labels = jnp.take_along_axis(own, pick, axis=1)
    kn = jax.random.split(fold_in_str(kd, "noise"), regions.shape[0])
    images = protos[labels] + jax.vmap(
        lambda kk: noise * jax.random.normal(kk, (n, H, W, C)))(kn)
    kt = fold_in_str(kd, "test")
    test_y = jax.random.randint(fold_in_str(kt, "labels"), (N_TEST,), 0, classes)
    test_x = protos[test_y] + noise * jax.random.normal(
        fold_in_str(kt, "noise"), (N_TEST, H, W, C))
    return images, labels, test_x, test_y


# ---- operations, for `mfu` (bench/flops.py) -----------------------------------

def forward_flops(shapes) -> int:
    """Multiply-adds x 2 of one image through the client model.

    3x3 SAME convolutions at full resolution, each followed by a 2x2 pool,
    then two dense layers.  Biases, ReLU and pooling are not counted.
    """
    h, w, c = shapes["image_shape"]
    flops = 0
    for out_c in shapes["channels"]:
        flops += 2 * h * w * 9 * c * out_c
        c, h, w = out_c, h // 2, w // 2
    flops += 2 * h * w * c * shapes["d_ff"]
    flops += 2 * shapes["d_ff"] * shapes["num_classes"]
    return flops


train_flops = eval_flops = forward_flops


def eval_samples(shapes) -> int:
    return N_TEST
