"""Pallas TPU kernel: fused server update (reduce + moments + AXPY).

The aggregator refactor (``fl/aggregators.py``) turns the server side of a
round into three flat sweeps: the weighted cohort reduction (K, P) -> (P,),
the first/second-moment EMA updates, and the parameter step.  Composed
from jnp primitives that is four-plus HBM walks over P-length vectors per
round; this kernel runs the whole chain in ONE P-blocked pass:

    delta_j = w @ U[:, j]  ->  (m, v) moment rules  ->  params += step

Geometry: grid over P in ``block_p`` columns (same walk as
``fedavg_reduce`` — ``pick_block_p`` budgets the (K, block_p) update tile;
the five extra (1, block_p) rows for params/m/v in+out add < 3% at the
cohort widths this engine sweeps).  The aggregator RULE is a traced
scalar: every registered rule is a couple of elementwise expressions, so
the kernel computes each rule's moments/step and selects branchlessly with
``jnp.where`` on the global ``AGGREGATOR_ORDER`` index — bit-for-bit the
expressions ``fl.aggregators`` traces through ``lax.switch``, just fused
behind the reduction instead of re-walking HBM per stage.

Bitwise contract: with identical inputs the kernel reproduces
``kernels.ref.server_update`` — ``ref.fedavg_reduce`` composed with
``aggregators.apply_rule`` — in interpret mode (tests/test_aggregators.py
sweeps every rule across padding-edge shapes).  The cohort WEIGHTS stay
outside: masking, sample-count weighting and the ``stale`` rule's
staleness discount are computed by the round core, so the kernel is a
pure function of (updates, weights, params, m, v, rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fedavg_reduce import mxu_precision


def _rule_math(agg, delta, p, m, v, eta, beta1, beta2, tau):
    """Branchless per-tile moment rules + parameter step (factored out of
    the kernel body — one source for the registry expressions).

    Global AGGREGATOR_ORDER indices (asserted against the registry by the
    traced wrappers below): 1 = fedavgm, 2 = fedadam, 3 = fedyogi; fedavg
    (0), stale (4) and fedbuff (5) are the plain AXPY with moments
    untouched (their discounts act in weight space before the reduce).
    """
    is_avgm = agg == 1.0
    is_adam = agg == 2.0
    is_yogi = agg == 3.0
    adaptive = is_adam | is_yogi
    m_new = jnp.where(
        is_avgm, beta1 * m + delta,
        jnp.where(adaptive, beta1 * m + (1.0 - beta1) * delta, m),
    )
    d2 = delta * delta
    v_new = jnp.where(
        is_adam, beta2 * v + (1.0 - beta2) * d2,
        jnp.where(is_yogi, v - (1.0 - beta2) * d2 * jnp.sign(v - d2), v),
    )
    step = jnp.where(
        adaptive, eta * m_new / (jnp.sqrt(v_new) + tau),
        jnp.where(is_avgm, eta * m_new, delta),
    )
    return p + step, m_new, v_new


def _update_kernel(eta, beta1, beta2, tau, precision, s_ref, w_ref, u_ref,
                   p_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref):
    # s: (1, 2) traced scalars [global agg index, round]; w: (1, K);
    # u: (K, bp) in ANY float dtype (bf16 update rows upcast in-tile, the
    # dot accumulates fp32); p/m/v: (1, bp) fp32 -> the params output
    # writes back in the MASTER dtype (po_ref's out_shape dtype), m/v fp32
    agg = s_ref[0, 0]
    delta = jnp.dot(
        w_ref[...], u_ref[...].astype(jnp.float32),
        precision=precision,
        preferred_element_type=jnp.float32,
    )
    po, mo, vo = _rule_math(
        agg, delta, p_ref[...], m_ref[...], v_ref[...], eta, beta1, beta2, tau
    )
    po_ref[...] = po.astype(po_ref.dtype)
    mo_ref[...] = mo
    vo_ref[...] = vo


@functools.partial(
    jax.jit,
    static_argnames=("eta", "beta1", "beta2", "tau", "block_p", "interpret"),
)
def server_update(
    updates: jax.Array,  # (K, P) flat cohort updates
    weights: jax.Array,  # (K,) masked + normalized cohort weights
    params: jax.Array,  # (P,) flat fp32 global model
    m: jax.Array,  # (P,) first-moment server state
    v: jax.Array,  # (P,) second-moment server state
    agg_idx: jax.Array,  # () int32 GLOBAL AGGREGATOR_ORDER index (traced)
    rnd: jax.Array,  # () int32 round counter (reserved for schedule rules)
    *,
    eta: float = 1.0,
    beta1: float = 0.9,
    beta2: float = 0.99,
    tau: float = 1e-3,
    block_p: int = 2048,
    interpret: bool = False,
):
    """Fused server update -> (params' in ``params.dtype``, m', v' fp32).

    Inputs upcast to fp32 rows in-tile (exact for bf16), the reduction and
    moment rules accumulate in fp32, and the params output downcasts to
    the master dtype on the final write — a no-op for the fp32 default
    lane (bitwise-frozen).
    """
    _assert_registry_order()
    K, P = updates.shape
    pp = (-P) % block_p
    up = jnp.pad(updates, ((0, 0), (0, pp)))
    row = lambda x: jnp.pad(x.astype(jnp.float32), (0, pp)).reshape(1, -1)
    w2 = weights.astype(jnp.float32).reshape(1, K)
    scalars = jnp.stack(
        [agg_idx.astype(jnp.float32), rnd.astype(jnp.float32)]
    ).reshape(1, 2)
    Pp = P + pp
    kernel = functools.partial(_update_kernel, eta, beta1, beta2, tau,
                               mxu_precision(interpret))
    p2, m2, v2 = pl.pallas_call(
        kernel,
        grid=(Pp // block_p,),
        in_specs=[
            pl.BlockSpec((1, 2), lambda j: (0, 0)),
            pl.BlockSpec((1, K), lambda j: (0, 0)),
            pl.BlockSpec((K, block_p), lambda j: (0, j)),
            pl.BlockSpec((1, block_p), lambda j: (0, j)),
            pl.BlockSpec((1, block_p), lambda j: (0, j)),
            pl.BlockSpec((1, block_p), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_p), lambda j: (0, j)),
            pl.BlockSpec((1, block_p), lambda j: (0, j)),
            pl.BlockSpec((1, block_p), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Pp), params.dtype),
            jax.ShapeDtypeStruct((1, Pp), jnp.float32),
            jax.ShapeDtypeStruct((1, Pp), jnp.float32),
        ],
        interpret=interpret,
    )(scalars, w2, up, row(params), row(m), row(v))
    return p2[0, :P], m2[0, :P], v2[0, :P]


def _assert_registry_order():
    """The branchless selects in ``_rule_math`` hardcode the registry
    order; fail loudly if it is ever reordered without touching this
    kernel."""
    from repro.fl.aggregators import AGGREGATOR_ORDER

    assert AGGREGATOR_ORDER == ("fedavg", "fedavgm", "fedadam", "fedyogi",
                                "stale", "fedbuff"), AGGREGATOR_ORDER


@functools.partial(
    jax.jit,
    static_argnames=("eta", "beta1", "beta2", "tau", "block_p", "interpret"),
)
def server_update_buffered(
    updates: jax.Array,  # (K, P) flat cohort updates (in-round survivors)
    weights: jax.Array,  # (K,) masked + normalized cohort weights
    buf: jax.Array,  # (Kb, P) in-flight delta ring buffer (RoundState leaf)
    buf_w: jax.Array,  # (Kb,) drained-slot weights (0 on undrained slots)
    params: jax.Array,  # (P,) flat fp32 global model
    m: jax.Array,  # (P,) first-moment server state
    v: jax.Array,  # (P,) second-moment server state
    agg_idx: jax.Array,  # () int32 GLOBAL AGGREGATOR_ORDER index (traced)
    rnd: jax.Array,  # () int32 round counter (reserved for schedule rules)
    drain: jax.Array,  # () bool: fold the buffer pre-reduce into delta
    *,
    eta: float = 1.0,
    beta1: float = 0.9,
    beta2: float = 0.99,
    tau: float = 1e-3,
    block_p: int = 2048,
    interpret: bool = False,
):
    """Fused buffered server update -> (params', m', v'), all (P,) fp32.

    The async-rounds (``fedbuff``) extension of ``server_update``: the
    ``(Kb, P)`` in-flight delta ring buffer rides the SAME P-blocked fused
    pass as the cohort — appended as Kb extra update rows whose weights
    (staleness discounts folded in by the round core) are gated by the
    traced ``drain`` flag in WEIGHT space, so the whole drained-buffer
    reduce is one augmented ``(K + Kb)``-row contraction per tile.  That
    single dot root is deliberate: an elementwise ``delta + buffer_delta``
    add lets the backend contract the buffer products into FMAs and drift
    off the oracle by an ulp, while the augmented contraction reproduces
    ``ref.server_update_buffered`` (the identical augmented
    ``fedavg_reduce``) bit for bit.  With ``drain=False`` the appended
    rows carry weight 0 — exact no-op additions, because round-to-nearest
    never yields a ``-0.0`` cohort delta (``x - x = +0.0``) — so every
    lane of a fedbuff-bearing registry can route through this one entry
    point unchanged.  Working set per program grows by the (Kb, block_p)
    buffer tile; the caller budgets ``pick_block_p(K + Kb, P)``.
    """
    wa = jnp.concatenate([
        weights.astype(jnp.float32),
        jnp.where(drain, buf_w.astype(jnp.float32), 0.0),
    ])
    # concat in the operands' common dtype (promotion, NOT a forced fp32
    # upcast): bf16 cohort rows + bf16 ring rows stay 2-byte through the
    # tile walk and upcast in-tile; the fp32 lane is unchanged (fp32 rows
    # promote to fp32, the historical layout)
    ua = jnp.concatenate([updates, buf], axis=0)
    return server_update(
        ua, wa, params, m, v, agg_idx, rnd, eta=eta, beta1=beta1,
        beta2=beta2, tau=tau, block_p=block_p, interpret=interpret,
    )
