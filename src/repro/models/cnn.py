"""The paper's FL task models: an MLP and two CNN sizes.

§IV-B trains "deep learning models with different sizes" on MNIST /
CIFAR-10 / SVHN; the exact nets are unspecified, so we use three standard
small image models whose parameter byte-sizes differ enough to exercise the
latency model (DESIGN.md §9).  Pure jnp (lax conv), params follow the
``Param`` convention so the FL runtime treats them like any other model.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init, zeros_init
from repro.sharding import Param


def _conv_init(key, shape, scale=1.0):
    # shape: (kh, kw, in, out)
    fan_in = shape[0] * shape[1] * shape[2]
    std = scale / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))
    w = std * jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
    return Param(w, (None, None, None, None))


def init_cnn(key, cfg) -> dict:
    """cfg.channels: conv channel progression; () => pure MLP."""
    H, W, C = cfg.image_shape
    ks = jax.random.split(key, 2 + 2 * max(len(cfg.channels), 1))
    params: dict[str, Any] = {"convs": []}
    in_c = C
    h, w = H, W
    for i, out_c in enumerate(cfg.channels):
        params["convs"].append(
            {
                "w": _conv_init(ks[i], (3, 3, in_c, out_c)),
                "b": zeros_init((out_c,), (None,)),
            }
        )
        in_c = out_c
        h, w = h // 2, w // 2  # 2x2 max-pool after each conv
    flat = h * w * in_c if cfg.channels else H * W * C
    params["fc1"] = {
        "w": dense_init(ks[-2], (flat, cfg.d_ff), (None, "mlp"), flat),
        "b": zeros_init((cfg.d_ff,), ("mlp",)),
    }
    params["fc2"] = {
        "w": dense_init(ks[-1], (cfg.d_ff, cfg.num_classes), ("mlp", "classes"), cfg.d_ff),
        "b": zeros_init((cfg.num_classes,), ("classes",)),
    }
    return params


def cnn_logits(params, cfg, images):
    """images (B,H,W,C), or (B, H*W*C) feature-minor rows -> logits
    (B, num_classes).

    Rows (the FL client store's layout, ``fl.partition.client_images``)
    are reshaped to ``cfg.image_shape`` first; for the MLP that reshape
    folds into its flatten.

    Activations follow the PARAM dtype (the fc2 leaf, representative of
    the whole tree): fp32 masters run the historical fp32 forward; the FL
    client's mixed-precision lane hands in bf16-cast params and the convs
    / matmuls run half-width end to end (``fl.client.make_local_trainer``
    holds loss and gradients in fp32).
    """
    x = images.reshape(images.shape[0], *cfg.image_shape)
    x = x.astype(params["fc2"]["w"].dtype)
    for conv in params["convs"]:
        x = jax.lax.conv_general_dilated(
            x, conv["w"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )
        x = jax.nn.relu(x + conv["b"][None, None, None, :])
        # a NumPy init value keeps this the differentiable max-pool: a
        # traced one turns it into a generic reduce_window, which has no VJP
        x = jax.lax.reduce_window(
            x, np.array(-np.inf, x.dtype), jax.lax.max,
            (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
        )
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    return x @ params["fc2"]["w"] + params["fc2"]["b"]


def cnn_loss(params, cfg, batch):
    """batch: images (B,H,W,C) or (B, H*W*C) rows, labels (B,).

    The cross-entropy accumulates in fp32 whatever the forward dtype (the
    logsumexp upcast is exact for bf16 logits and a no-op for fp32).
    """
    logits = cnn_logits(params, cfg, batch["images"]).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
    loss = jnp.mean(logz - gold)
    acc = jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))
    return loss, {"ce": loss, "accuracy": acc}
