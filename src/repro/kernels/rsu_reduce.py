"""Pallas TPU kernel: segment-reduce client updates by RSU attachment.

The two-tier aggregation path (edge aggregation: clients reduce into their
attached RSU, RSUs reduce into the server) needs per-RSU partial sums of
the weighted (K clients x P params) update matrix, segmented by the
attachment ids the ``rttg_latency`` chain already computes.  Done naively
that is R separate masked reductions over the same HBM-resident matrix;
this kernel produces all R partials (plus the per-RSU weight masses the
server-level normalization reads) in ONE tiled sweep:

    partials[r, p] = sum_k  w[k] * [rid[k] == r] * updates[k, p]
    mass[r]       = sum_k  w[k] * [rid[k] == r]

Geometry: grid ``(P/block_p, K/block_k)`` — the k-axis is the innermost
walk, so for each column tile the (Rp, block_p) partial-sum accumulator
stays resident in VMEM scratch across all k-blocks (the same
scratch-accumulator trick as ``rttg_latency``'s phase-0 load counts;
``Rp`` pads the RSU axis to the 128-lane minimum).  Each grid step builds
the (block_k, Rp) one-hot routing matrix ``m = onehot(rid) * w`` on the
fly and contracts it against the update tile on the MXU; the (1, Rp) mass
row is the column sum of ``m``, accumulated once per k-walk (first column
tile only).  Out blocks map to constant indices along k, so every visit
writes the current accumulator value and the final visit leaves the
complete sum.

VMEM per program: the (block_k, block_p) update tile + the (Rp, block_p)
accumulator + the (block_k, Rp) routing tile — ``(block_k + Rp) * block_p
* 4 B`` to first order; ``kernels.ops.rsu_reduce_auto`` sizes the tiles so
this stays under the shared ``FEDAVG_VMEM_BUDGET``.

Bitwise contract: with a single k-block (the default, ``block_k=None`` ->
``block_k=K``) the kernel reproduces ``kernels.ref.rsu_reduce`` bit for
bit — same one-hot expression, same single contraction.  A k-blocked walk
(fleet-scale cohorts) reassociates each per-RSU sum across k-blocks: it
equals the composition of per-chunk references summed in k-block order
(exact for integer-valued operands, allclose in general) — the parity
suite in tests/test_hierarchical.py pins both contracts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fedavg_reduce import mxu_precision

_LANE = 128  # TPU lane width: minimum last-dim tile


def _seg_kernel(precision, w_ref, rid_ref, u_ref, part_ref, mass_ref, acc_ref,
                macc_ref):
    """One grid step: (p-tile, k-block).  Scratch persists across k."""
    kb = pl.program_id(1)
    first_p = pl.program_id(0) == 0
    bk = u_ref.shape[0]
    rp = acc_ref.shape[0]

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((kb == 0) & first_p)
    def _init_mass():
        macc_ref[...] = jnp.zeros_like(macc_ref)

    rid = rid_ref[...]  # (bk, 1) int32 column, same layout as the u tile
    w = w_ref[...]  # (bk, 1) f32
    onehot = jax.lax.broadcasted_iota(jnp.int32, (bk, rp), 1) == rid
    m = onehot.astype(jnp.float32) * w  # (bk, Rp) routing matrix
    # MXU: contract the cohort axis — (Rp, bk) x (bk, bp) -> (Rp, bp)
    acc_ref[...] += jax.lax.dot_general(
        m, u_ref[...].astype(jnp.float32),
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32,
    )

    @pl.when(first_p)
    def _mass():
        macc_ref[...] += jnp.sum(m, axis=0, keepdims=True)

    # constant out-block indices along k: every visit writes the current
    # accumulator (downcast to the partials' output dtype — identity for
    # the fp32 default); the last k-visit leaves the complete sum
    part_ref[...] = acc_ref[...].astype(part_ref.dtype)
    mass_ref[...] = macc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("n_rsu", "block_p", "block_k", "interpret", "out_dtype"),
)
def rsu_reduce(
    updates: jax.Array,  # (K, P) client update vectors
    weights: jax.Array,  # (K,) aggregation weights (masked slots carry 0)
    rid: jax.Array,  # (K,) int32 attached-RSU id per cohort slot
    n_rsu: int,
    *,
    block_p: int = 2048,
    block_k: int | None = None,
    interpret: bool = False,
    out_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Segment-reduce by attachment -> (partials (R, P), mass (R,) f32).

    The accumulator is ALWAYS fp32 VMEM scratch (bf16 update tiles upcast
    in-tile); ``out_dtype`` (default fp32) only picks the partials' output
    dtype — the bf16 lane's chunk carry rides half-width partials.
    """
    K, P = updates.shape
    out_dtype = jnp.float32 if out_dtype is None else out_dtype
    bk = K if block_k is None else min(block_k, K)
    pad_k = (-K) % bk
    pad_p = (-P) % block_p
    rp = max(_LANE, -(-n_rsu // _LANE) * _LANE)
    # padded cohort slots carry weight 0 (their routing row is exactly
    # zero); padded RSU lanes are never attached, so both slice away clean
    up = jnp.pad(updates, ((0, pad_k), (0, pad_p)))
    w2 = jnp.pad(weights.astype(jnp.float32), (0, pad_k)).reshape(-1, 1)
    rid2 = jnp.pad(rid.astype(jnp.int32), (0, pad_k)).reshape(-1, 1)
    Kp, Pp = K + pad_k, P + pad_p
    partials, mass = pl.pallas_call(
        functools.partial(_seg_kernel, mxu_precision(interpret)),
        grid=(Pp // block_p, Kp // bk),
        in_specs=[
            pl.BlockSpec((bk, 1), lambda p, k: (k, 0)),
            pl.BlockSpec((bk, 1), lambda p, k: (k, 0)),
            pl.BlockSpec((bk, block_p), lambda p, k: (k, p)),
        ],
        out_specs=[
            pl.BlockSpec((rp, block_p), lambda p, k: (0, p)),
            pl.BlockSpec((1, rp), lambda p, k: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, Pp), out_dtype),
            jax.ShapeDtypeStruct((1, rp), jnp.float32),
        ],
        scratch_shapes=[_scratch((rp, block_p)), _scratch((1, rp))],
        interpret=interpret,
    )(w2, rid2, up)
    return partials[:n_rsu, :P], mass[0, :n_rsu]


def _scratch(shape):
    """VMEM scratch allocator that also works under interpret on CPU."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)
