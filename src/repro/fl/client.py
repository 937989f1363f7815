"""Client-side local training, vmapped over the selected cohort.

TPU adaptation (DESIGN.md §3): the paper trains PyTorch clients one by one;
here the whole cohort is one SPMD program — local SGD is a ``lax.scan`` over
steps, ``vmap``-ed over the cohort axis, so on a pod the cohort shards over
the ``data`` mesh axis.  De-selected cohort slots carry weight 0 and are
masked out of the aggregate.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.utils import flatten_to_vector, tree_sub


def make_local_trainer(
    loss_fn: Callable,
    lr: float,
    epochs: int,
    batch_size: int,
    mu: float = 0.0,
    compute_dtype=None,
) -> Callable:
    """Build jit'd cohort trainer.

    Returned fn: (global_params, images (..., n, D), labels (..., n), key,
                  rows=None, valid=None)
      -> (updates pytree with leading K, update_vecs (K, P_flat))

    ``images`` is a store of lane-dense sample rows, D = H*W*C features
    minor (``partition.client_images``); ``labels`` matches it without the
    feature axis.  ``rows`` is a tuple of leading-axis indices, each
    broadcastable to (K,): cohort slot k trains on the n samples at
    ``images[rows[0][k], rows[1][k], ...]`` — ``(idx_c,)`` for one store,
    ``(data_idx, idx_c)`` for the engine's stacked dedup rows; the default
    ``(arange(K),)`` trains a (K, n, D) store row by row.  Each SGD step
    gathers its batch_size rows straight from the store by that index and
    the step's permuted sample ids, so no per-slot copy of a client's
    shard is ever made.  ``valid`` (K,) bool masks padding slots: their
    gathered rows are multiplied by 0 and their labels set to 0.  The
    model takes the (batch_size, D) rows and reshapes them to its image
    shape.

    ``mu`` is the FedProx proximal coefficient: each local step descends
    ``loss + (mu/2) ||p - p_global||^2``, i.e. the traced gradient gains
    ``mu * (p - p_global)`` pulling drifting clients back toward the
    global model (Li et al., FedProx) — the standard non-iid stabilizer
    the aggregator axis is swept against.  The ``mu == 0`` gate is
    STATIC: the default program contains no proximal term at all, so
    plain FedAvg local SGD stays bitwise-identical by construction.

    ``compute_dtype`` (a jnp dtype, or None = fp32) is the mixed-precision
    lane, the ``models/layers.py`` zoo idiom lifted into the FL client:
    each loss/grad evaluation casts the fp32 master params down to
    ``compute_dtype`` INSIDE the differentiated closure, so the forward
    pass (and the model's activations, which follow the param dtype) runs
    half-width while the cast's VJP hands fp32 cotangents back to the fp32
    master — fp32 loss/grad accumulation, fp32 SGD state.  The ``None``
    gate is STATIC like ``mu``: the default program contains no casts at
    all and stays bitwise-identical.
    """
    cast = None
    if compute_dtype is not None and compute_dtype != jnp.float32:
        cast = lambda tree: jax.tree_util.tree_map(
            lambda w: w.astype(compute_dtype), tree
        )

    def local_sgd(global_params, images, labels, row, valid, key):
        n = labels.shape[-1]
        spe = max(n // batch_size, 1)
        perm_keys = jax.random.split(key, epochs)
        idx = jax.vmap(lambda k: jax.random.permutation(k, n)[: spe * batch_size])(
            perm_keys
        )  # (epochs, spe*bs)
        idx = idx.reshape(epochs * spe, batch_size)

        def step(p, bidx):
            # one whole (D,) row per sample, gathered from the store itself
            x, y = images[(*row, bidx)], labels[(*row, bidx)]
            if valid is not None:
                x = x * valid
                y = jnp.where(valid, y, 0)
            batch = {"images": x, "labels": y}
            if cast is None:
                fwd = lambda pp: loss_fn(pp, batch)[0]
            else:
                fwd = lambda pp: loss_fn(cast(pp), batch)[0]
            g = jax.grad(fwd)(p)
            if mu:
                g = jax.tree_util.tree_map(
                    lambda gw, w, w0: gw + mu * (w - w0), g, p, global_params
                )
            p = jax.tree_util.tree_map(lambda w, gw: w - lr * gw, p, g)
            return p, None

        params, _ = jax.lax.scan(step, global_params, idx)
        return params

    @jax.jit
    def train_cohort(global_params, images, labels, key, rows=None, valid=None):
        if rows is None:
            rows = (jnp.arange(images.shape[0]),)
        K = jnp.broadcast_shapes(*(jnp.shape(r) for r in rows))[0]
        rows = tuple(jnp.broadcast_to(r, (K,)) for r in rows)
        # ``key`` is either one cohort key (split K ways here — the
        # historical behavior, bitwise-frozen) or an already-split (K,)
        # per-client key array: the chunk-streamed hierarchical lane splits
        # ONCE for the full cohort and slices per chunk, so each client
        # consumes the same key it would in the unblocked lane.
        keys = key if key.ndim == 1 else jax.random.split(key, K)
        new_params = jax.vmap(
            lambda r, v, k: local_sgd(global_params, images, labels, r, v, k)
        )(rows, valid, keys)
        updates = jax.tree_util.tree_map(
            lambda new, old: new - old[None], new_params, global_params
        )
        vecs = jax.vmap(lambda i: flatten_to_vector(
            jax.tree_util.tree_map(lambda u: u[i], updates)
        )[0])(jnp.arange(K))
        return updates, vecs

    return train_cohort
