"""Mixture-of-Experts FFN: capacity-bounded dispatch, expert-parallel.

TPU adaptation (DESIGN.md §3/§4): instead of a CUDA grouped-GEMM, tokens are
routed with a *static-shape* scatter into per-expert capacity buffers
``(E, C, d)`` and processed with one batched einsum on the MXU.

Two dispatch paths:

``_moe_gspmd``  — single-program scatter; GSPMD infers the collectives.
  Baseline path (and the only path without an ambient mesh — smoke tests).
  The dry-run measured it collective-bound by ~100x (EXPERIMENTS.md §Perf):
  GSPMD turns the global scatter into TB-scale all-reduces.

``_moe_shard_map`` — explicit expert parallelism (the §Perf optimized path):
  tokens stay sharded over (pod, data); every model-rank holds the same
  local tokens, routes them LOCALLY (one-hot cumsum — no communication),
  keeps only the copies destined to its own experts (E >= tp: expert-
  sharded; E < tp: all experts with an ff-slice, mixtral), applies the
  expert SwiGLU, and the ONLY collective is one fp32 psum of the combined
  output over the model axis — the same wire cost as a dense TP MLP layer.

Top-k routing follows Mixtral: softmax over the full expert set, take top-k,
renormalize the selected gates.  Tokens beyond an expert's capacity are
dropped (capacity factor 1.25); the auxiliary load-balance loss keeps drop
rates low.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init
from repro.sharding import act_shard
from repro.sharding.context import _STATE as _SHARD_STATE


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def init_moe(key, cfg, num_layers: int, dtype):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    kr, kg, ku, kd = jax.random.split(key, 4)
    L = num_layers
    return {
        "router": dense_init(kr, (L, d, E), ("layers", "embed", None), d, jnp.float32),
        "w_gate": dense_init(kg, (L, E, d, ff), ("layers", "experts", "embed", "expert_mlp"), d, dtype),
        "w_up": dense_init(ku, (L, E, d, ff), ("layers", "experts", "embed", "expert_mlp"), d, dtype),
        "w_down": dense_init(kd, (L, E, ff, d), ("layers", "experts", "expert_mlp", "embed"), ff, dtype),
    }


def moe_ffn(p, x, cfg, capacity_factor: float = 1.25):
    """x: (B, S, d) -> (y, aux_loss).  Params ``p`` are one layer's slice.

    Dispatches to the explicit shard_map expert-parallel path when a
    production mesh is ambient (launchers install it), else the GSPMD path.
    """
    mesh = _SHARD_STATE["mesh"]
    if mesh is not None and dict(mesh.shape).get("model", 1) > 1:
        return _moe_shard_map(p, x, cfg, mesh, capacity_factor)
    return _moe_gspmd(p, x, cfg, capacity_factor)


def _moe_gspmd(p, x, cfg, capacity_factor: float = 1.25):
    """Single-program scatter dispatch (baseline; see module docstring)."""
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    N = B * S
    xt = x.reshape(N, d)

    # --- routing (fp32) ---
    logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (N, E)
    gates, eidx = jax.lax.top_k(probs, K)  # (N, K)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch/Mixtral form)
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    assign = jax.nn.one_hot(eidx, E, dtype=jnp.float32).sum(axis=1)  # (N, E)
    ce = jnp.mean(assign, axis=0) / K  # fraction of tokens per expert
    aux = E * jnp.sum(me * ce)

    # --- capacity-bounded dispatch ---
    C = _round_up(max(int(capacity_factor * K * N / E), 1), 128)
    C = min(C, _round_up(N, 128))
    flat_e = eidx.reshape(N * K)  # expert id per token-copy
    flat_g = gates.reshape(N * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (NK, E)
    pos_all = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos_all, flat_e[:, None], axis=1)[:, 0]  # (NK,)
    keep = pos < C
    slot = jnp.where(keep, pos, C - 1)
    tok = jnp.arange(N * K) // K

    src = jnp.where(keep[:, None], xt[tok], 0).astype(x.dtype)  # (NK, d)
    buf = jnp.zeros((E, C, d), x.dtype)
    buf = buf.at[flat_e, slot].add(src, mode="drop")
    buf = act_shard(buf, "experts", "expert_cap", None)

    # --- expert FFN (SwiGLU) on the MXU ---
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))
    out_e = act_shard(out_e, "experts", "expert_cap", None)

    # --- combine ---
    y_cp = out_e[flat_e, slot].astype(jnp.float32)  # (NK, d)
    y_cp = y_cp * (flat_g * keep.astype(jnp.float32))[:, None]
    y = jnp.sum(y_cp.reshape(N, K, d), axis=1)
    return y.reshape(B, S, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# explicit expert-parallel dispatch (§Perf optimized path)
# ---------------------------------------------------------------------------


def _route_local(xt, router_w, E, K):
    """Local routing: gates/expert ids + capacity slots.  Zero collectives."""
    n = xt.shape[0]
    logits = jnp.einsum(
        "nd,de->ne", xt.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, K)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    flat_e = eidx.reshape(n * K)
    flat_g = gates.reshape(n * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    # load-balance aux (local shard statistics; pmean'd by the caller)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(eidx, E, dtype=jnp.float32).sum(axis=1), axis=0) / K
    aux = E * jnp.sum(me * ce)
    return flat_e, flat_g, slot, aux


def _moe_shard_map(p, x, cfg, mesh, capacity_factor: float = 1.25):
    """Expert-parallel MoE: local routing, one output psum over 'model'.

    Token layout: every model-rank holds the same (pod,data)-shard of
    tokens.  E >= tp: rank r owns experts [r*E/tp, (r+1)*E/tp) and scatters
    only copies routed to them (others masked to zero weight).  E < tp
    (mixtral, 8e on tp=16): every rank processes all experts over an
    ff-slice; the down-projection partial sums merge in the same psum that
    the E >= tp case uses for combining expert outputs.
    """
    E, K = cfg.num_experts, cfg.experts_per_token
    B, S, d = x.shape
    sizes = dict(mesh.shape)
    tp = sizes.get("model", 1)
    data_axes = tuple(a for a in ("pod", "data") if a in sizes)
    dp = 1
    for a in data_axes:
        dp *= sizes[a]
    shard_tokens = dp > 1 and B % dp == 0
    batch_spec = P(data_axes if shard_tokens else None, None, None)
    expert_sharded = E % tp == 0
    # weight specs must match the rule-table shardings (rules.py)
    wg_spec = P("model", None, None) if expert_sharded else P(None, None, "model")
    wd_spec = P("model", None, None) if expert_sharded else P(None, "model", None)

    def local_fn(router_w, wg, wu, wd, xl):
        Bl, Sl, dl = xl.shape
        n = Bl * Sl
        xt = xl.reshape(n, dl)
        flat_e, flat_g, slot, aux = _route_local(xt, router_w, E, K)
        C = _round_up(max(int(capacity_factor * K * n / E), 1), 8)
        C = min(C, _round_up(n * K, 8))
        keep = slot < C
        slot = jnp.where(keep, slot, C - 1)
        tok = jnp.arange(n * K) // K

        if expert_sharded:
            e_loc = E // tp
            r = jax.lax.axis_index("model")
            mine = (flat_e // e_loc) == r
            le = jnp.where(mine, flat_e % e_loc, 0)
            use = keep & mine
            buf = jnp.zeros((e_loc, C, dl), xl.dtype)
            src = jnp.where(use[:, None], xt[tok], 0).astype(xl.dtype)
            buf = buf.at[le, slot].add(jnp.where(use[:, None], src, 0), mode="drop")
        else:
            le = flat_e
            use = keep
            buf = jnp.zeros((E, C, dl), xl.dtype)
            src = jnp.where(use[:, None], xt[tok], 0).astype(xl.dtype)
            buf = buf.at[le, slot].add(src, mode="drop")

        g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(xl.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(xl.dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xl.dtype) * u
        out_e = jnp.einsum("ecf,efd->ecd", h, wd.astype(xl.dtype))

        y_cp = out_e[le, slot]
        y_cp = y_cp * (flat_g * use.astype(jnp.float32))[:, None].astype(y_cp.dtype)
        y = jnp.sum(y_cp.reshape(n, K, dl), axis=1)
        # the ONLY collective: merge expert outputs (and ff partials) over tp
        y = jax.lax.psum(y, "model")
        if shard_tokens:
            aux = jax.lax.pmean(aux, data_axes)
        return y.reshape(Bl, Sl, dl).astype(xl.dtype), aux

    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, None), wg_spec, wg_spec, wd_spec, batch_spec),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    return y, aux
