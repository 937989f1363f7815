"""Pallas TPU kernel: fused RTTG -> latency geometry chain (round hot path).

Every FL round evaluates the per-client geometry chain twice — once on the
*fused, predicted* topology (stage 2: elect on where clients WILL be) and
once on the *true, evolved* topology (mid-round: what uploads actually
cost).  Composed from jnp primitives that chain makes five-plus separate
N-vector / (N, R) sweeps over HBM per pass (prediction loop, ring
distances, masked argmin, load counts, SNR, Shannon rate, queue/handover
terms) plus an (N, N) adjacency the selector never reads.  This kernel runs
the whole chain in ONE tiled pass:

    [predict n Euler steps] -> RSU attach (masked argmin over rsu_up_mask)
      -> per-RSU load counts -> SNR/latency model -> connectivity

Geometry: grid ``(2, N/block_n)`` — a two-phase walk over N-blocks with the
R-dimension resident per program.  Phase 0 attaches each block and
accumulates per-RSU load counts into a VMEM scratch accumulator (the only
cross-block quantity in the chain); phase 1 re-runs the (cheap, elementwise)
predict+attach recompute and finishes the latency/connectivity math against
the now-complete counts.  The recompute doubles the VPU work but keeps the
kernel a single launch with one tiny (1, Rp) scratch — the chain is
memory-bound, and inputs are only ~5 N-vectors.

VMEM per program: ~4 * block_n * Rp * 4 B for the (block_n, Rp) distance /
one-hot tiles (block_n=256, Rp=128 -> 0.5 MB) plus the N-vector blocks —
far under the 16 MB budget.  ``Rp`` pads the RSU axis to the 128-lane
minimum; padded RSUs are masked dark so they never win the attachment
argmin (exactly how ``rsu_outage`` masks real RSUs).

Bitwise contract: with identical inputs the kernel reproduces
``kernels.ref.rttg_latency`` — the composition of the core pure forms
(``predict_kinematics`` -> ``rsu_geometry`` -> ``latency_from_geometry`` /
``connected_from_snr``) — bit for bit in interpret mode: every stage uses
the same expressions in the same order, and the load counts are
integer-valued floats, so the counts-then-gather layout here equals the
reference's (N, N) comparison sum exactly.  PRNG stays OUTSIDE the kernel:
the connection-rate Bernoulli mask is drawn by the caller and passed in as
``forced``, which is what keeps the fused and unfused round paths bitwise
comparable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.rttg import n_rsu_of, rsu_up_mask
from repro.core.trajectory import horizon_steps

# packed traced-scalar layout (one (1, S) f32 operand; see _pack_scalars)
_SCALARS = (
    "t", "model_bytes", "ring_length_m", "rsu_spacing_m", "ou_theta",
    "mean_speed_mps", "carrier_ghz", "eirp_dbm", "noise_dbm", "snr_min_db",
    "bandwidth_hz", "overhead_bytes", "backhaul_s", "queue_s_per_vehicle",
    "rush_amp", "rush_period_s", "day_amp", "day_period_s", "day_harmonic2",
)
_S = len(_SCALARS)
_LANE = 128  # TPU lane width: minimum last-dim tile


def _pack_scalars(t, model_bytes, cfg) -> jax.Array:
    vals = {"t": t, "model_bytes": model_bytes}
    row = [
        jnp.asarray(vals.get(name, getattr(cfg, name, 0.0)), jnp.float32)
        for name in _SCALARS
    ]
    return jnp.stack(row).reshape(1, _S)


def _chain_kernel(n_clients, n_rsu, n_steps, dt, horizon_s, want_rid,
                  s_ref, mask_ref, pos_ref, speed_ref, accel_ref, forced_ref,
                  lat_ref, conn_ref, *rest):
    """One grid step: (phase, j) over the two-phase N-block walk.

    ``rest`` is (rid_ref,) counts_ref — the optional attachment-id output
    (``want_rid``) slots in before the scratch accumulator.
    """
    rid_ref = rest[0] if want_rid else None
    counts_ref = rest[-1]
    phase = pl.program_id(0)
    j = pl.program_id(1)
    bn = pos_ref.shape[0]

    s = {name: s_ref[0, k] for k, name in enumerate(_SCALARS)}
    pos, speed, accel = pos_ref[...], speed_ref[...], accel_ref[...]  # (bn, 1)

    # ---- stage 2 (optional): the OU-mean Euler predictor, n_steps static.
    # Same expressions, same order as core.trajectory.predict_kinematics.
    if n_steps:
        def body(_, carry):
            pos, speed, accel = carry
            accel = accel * (1.0 - s["ou_theta"] * dt)
            speed = jnp.clip(speed + accel * dt, 1.0, 3.0 * s["mean_speed_mps"])
            pos = jnp.mod(pos + speed * dt, s["ring_length_m"])
            return (pos, speed, accel)

        pos, speed, accel = jax.lax.fori_loop(0, n_steps, body, (pos, speed, accel))
    t_eff = s["t"] + horizon_s if n_steps else s["t"]

    # ---- RSU attachment: masked argmin over the (bn, Rp) ring distances.
    rp = mask_ref.shape[1]
    # Mosaic has only an integer iota; the cast is exact for lane ids < 2**24
    rsu_pos = (
        jax.lax.broadcasted_iota(jnp.int32, (1, rp), 1).astype(jnp.float32)
        * s["rsu_spacing_m"]
    )
    d = jnp.abs(pos - rsu_pos)  # (bn, Rp); broadcast against (1, Rp)
    d = jnp.minimum(d, s["ring_length_m"] - d)
    live = mask_ref[...] != 0.0  # dark + padded RSUs never win
    d = jnp.where(live, d, jnp.inf)
    rid = jnp.argmin(d, axis=1, keepdims=True)  # (bn, 1) int32
    row = jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0) + j * bn
    valid = row < n_clients  # padded client rows
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (bn, rp), 1) == rid
    ) & valid  # (bn, Rp)

    @pl.when(phase == 0)
    def _accumulate():
        @pl.when(j == 0)
        def _init():
            counts_ref[...] = jnp.zeros_like(counts_ref)

        counts_ref[...] += jnp.sum(
            onehot.astype(jnp.float32), axis=0, keepdims=True
        )
        # the out blocks are visited in both phases; give the phase-0 visit
        # a defined value (phase 1 overwrites with the real results)
        lat_ref[...] = jnp.zeros_like(lat_ref)
        conn_ref[...] = jnp.zeros_like(conn_ref)
        if want_rid:
            rid_ref[...] = jnp.zeros_like(rid_ref)

    @pl.when(phase == 1)
    def _finish():
        d_min = jnp.min(d, axis=1, keepdims=True)  # == d[argmin], exactly
        dist3d = jnp.sqrt(d_min**2 + 15.0**2 + 5.0**2)
        # integer-exact gather of this block's per-client load
        load = jnp.sum(
            onehot.astype(jnp.float32) * counts_ref[...], axis=1, keepdims=True
        )
        # ---- network.latency_from_geometry, expression for expression ----
        dmax = jnp.maximum(dist3d, 1.0)
        pl_db = 32.4 + 20.0 * jnp.log10(s["carrier_ghz"]) + 30.0 * jnp.log10(dmax)
        snr = s["eirp_dbm"] - pl_db - s["noise_dbm"]
        snr_lin = jnp.power(10.0, snr / 10.0)
        # congestion_factor(t_eff) * day_envelope, as in core.rttg
        x_day = jnp.pi * t_eff / jnp.maximum(s["day_period_s"], 1e-3)
        s1, s2 = jnp.sin(x_day), jnp.sin(2.0 * x_day)
        day_env = 1.0 + s["day_amp"] * (s1 * s1 + s["day_harmonic2"] * s2 * s2)
        ph = jnp.sin(jnp.pi * t_eff / jnp.maximum(s["rush_period_s"], 1e-3))
        congestion = 1.0 + s["rush_amp"] * ph * ph * day_env
        load_eff = load * congestion
        rate = (
            s["bandwidth_hz"] / jnp.maximum(load_eff, 1.0)
            * jnp.log2(1.0 + snr_lin)
        )
        rate = jnp.maximum(rate, 1e4)
        payload_bits = 8.0 * (s["model_bytes"] + s["overhead_bytes"])
        t_air = 2.0 * payload_bits / rate
        t_prop = 2.0 * dist3d / 299_792_458.0 + 2.0 * s["backhaul_s"]
        t_queue = s["queue_s_per_vehicle"] * load_eff
        edge = dist3d / (0.5 * s["rsu_spacing_m"])
        t_ho = 0.2 * jnp.clip(edge - 0.7, 0.0, 1.0) * speed / s["mean_speed_mps"]
        lat_ref[...] = t_air + t_prop + t_queue + t_ho
        conn_ref[...] = jnp.where(
            (snr >= s["snr_min_db"]) & (forced_ref[...] != 0.0), 1.0, 0.0
        )
        if want_rid:
            # the attachment argmin this phase already resolved, exported
            # for the hierarchical round path (f32 block; cast outside)
            rid_ref[...] = rid.astype(jnp.float32)


def rttg_latency(
    pos: jax.Array,  # (N,) fused/true arc positions
    speed: jax.Array,  # (N,)
    accel: jax.Array,  # (N,)
    t,  # scalar snapshot time (traced)
    model_bytes,  # scalar payload bytes (traced)
    forced: jax.Array | None,  # (N,) bool Bernoulli CR mask, or None
    cfg,  # TrafficConfig | ScenarioParams (duck-typed)
    *,
    predict: bool,  # True = stage-2 pass (run the horizon predictor)
    want_rid: bool = False,  # append the (N,) int32 attachment ids
    block_n: int = 256,
    interpret: bool = False,
):
    """Fused geometry chain -> (latency (N,) f32, connected (N,) bool).

    ``want_rid=True`` appends the (N,) int32 attachment ids as a third
    output (the argmin phase 1 already resolves; adding the output leaves
    the latency/connectivity expressions untouched, so the two-output view
    stays bitwise-frozen).  A concrete ``TrafficConfig`` is lifted to its
    traced ``ScenarioParams`` view HERE, outside the jit boundary — the
    config dataclass is not a pytree, so it cannot cross into the jitted
    wrapper as an argument.
    """
    from repro.config import TrafficConfig

    if isinstance(cfg, TrafficConfig):
        from repro.core.scenarios import scenario_params

        cfg = scenario_params(cfg)
    return _rttg_latency(
        pos, speed, accel, t, model_bytes, forced, cfg,
        predict=predict, want_rid=want_rid, block_n=block_n,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("predict", "want_rid", "block_n", "interpret")
)
def _rttg_latency(
    pos, speed, accel, t, model_bytes, forced, cfg, *,
    predict: bool, want_rid: bool, block_n: int, interpret: bool,
):
    N = pos.shape[0]
    R = n_rsu_of(cfg)
    n_steps = horizon_steps(cfg.predict_horizon_s, cfg) if predict else 0
    horizon_s = float(cfg.predict_horizon_s) if predict else 0.0
    dt = float(cfg.sim_dt_s)

    bn = min(block_n, max(8, 1 << (N - 1).bit_length()))
    pad_n = (-N) % bn
    rp = max(_LANE, -(-R // _LANE) * _LANE)

    def col(x):
        return jnp.pad(x.astype(jnp.float32), (0, pad_n)).reshape(-1, 1)

    if forced is None:
        forced = jnp.ones((N,), bool)
    mask = jnp.pad(rsu_up_mask(cfg).astype(jnp.float32), (0, rp - R)).reshape(1, rp)
    scalars = _pack_scalars(t, model_bytes, cfg)

    nb = (N + pad_n) // bn
    kernel = functools.partial(
        _chain_kernel, N, R, n_steps, dt, horizon_s, want_rid
    )
    n_out = 3 if want_rid else 2
    outs = pl.pallas_call(
        kernel,
        grid=(2, nb),
        in_specs=[
            pl.BlockSpec((1, _S), lambda p, j: (0, 0)),
            pl.BlockSpec((1, rp), lambda p, j: (0, 0)),
            pl.BlockSpec((bn, 1), lambda p, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda p, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda p, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda p, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda p, j: (j, 0)) for _ in range(n_out)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N + pad_n, 1), jnp.float32)
            for _ in range(n_out)
        ],
        scratch_shapes=[_scratch((1, rp))],
        interpret=interpret,
    )(scalars, mask, col(pos), col(speed), col(accel), col(forced))
    lat, conn = outs[0], outs[1]
    if want_rid:
        return lat[:N, 0], conn[:N, 0] != 0.0, outs[2][:N, 0].astype(jnp.int32)
    return lat[:N, 0], conn[:N, 0] != 0.0


def _scratch(shape):
    """VMEM scratch allocator that also works under interpret on CPU."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)
