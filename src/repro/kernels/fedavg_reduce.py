"""Pallas TPU kernel: FedAvg weighted reduction (stage-4/server hot spot).

Aggregation contracts a (K clients x P params) update matrix against cohort
weights — arithmetic intensity ~1 flop/byte, firmly memory-bound.  The
kernel's job is a single HBM sweep of the update matrix with the weight
vector resident in VMEM, instead of K separate AXPY sweeps (the naive
pytree approach): a (1, K) x (K, block_p) matmul per grid step.

Geometry: grid over P in ``block_p`` columns; per-program VMEM =
K * block_p * 4 B (K<=256, block_p=2048 -> 2 MB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def mxu_precision(interpret: bool):
    """Dot precision of the FL reductions' fp32 contract.

    Compiled for a TPU, Mosaic contracts an f32 dot in one bf16 MXU pass
    (~2**-9 relative) unless asked for HIGHEST.  Interpret mode runs on
    the CPU, where an f32 dot is fp32 anyway: the default there keeps
    XLA's fusion of the kernel body, and its bitwise parity with the
    refs, as it was.
    """
    return None if interpret else jax.lax.Precision.HIGHEST


def _reduce_kernel(precision, w_ref, u_ref, o_ref):
    # w: (1, K), u: (K, bp) -> o: (1, bp)
    o_ref[...] = jnp.dot(
        w_ref[...], u_ref[...].astype(jnp.float32),
        precision=precision,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def fedavg_reduce(
    updates: jax.Array,  # (K, P)
    weights: jax.Array,  # (K,)
    *,
    block_p: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """Weighted sum over the cohort axis -> (P,) fp32."""
    K, P = updates.shape
    pp = (-P) % block_p
    up = jnp.pad(updates, ((0, 0), (0, pp)))
    w2 = weights.astype(jnp.float32).reshape(1, K)
    Pp = P + pp
    out = pl.pallas_call(
        functools.partial(_reduce_kernel, mxu_precision(interpret)),
        grid=(Pp // block_p,),
        in_specs=[
            pl.BlockSpec((1, K), lambda j: (0, 0)),
            pl.BlockSpec((K, block_p), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_p), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, Pp), jnp.float32),
        interpret=interpret,
    )(w2, up)
    return out[0, :P]
