"""Mixture-of-Experts FFN: GShard-style capacity-based dispatch.

Supports DeepSeekMoE fine-grained experts (2 shared + 64 routed top-6) and
Mixtral (8 experts top-2).  The dispatch/combine einsums shard the expert
axis over the 'model' mesh axis (expert parallelism) — GSPMD lowers them to
the all-to-all traffic the paper's Alltoall collective benchmark models.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import ModelCfg, init_mlp, apply_mlp
from repro.models import common as _common

from jax import shard_map


def init_moe(key, cfg: ModelCfg):
    me = cfg.moe
    d, dfe = cfg.d_model, me.d_ff_expert
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    s, s2 = float(1.0 / np.sqrt(d)), float(1.0 / np.sqrt(dfe))
    p = {
        "router": jax.random.normal(k1, (d, me.n_experts), jnp.float32) * s,
        "w_gate": jax.random.normal(k2, (me.n_experts, d, dfe), cfg.dtype) * s,
        "w_up": jax.random.normal(k3, (me.n_experts, d, dfe), cfg.dtype) * s,
        "w_down": jax.random.normal(k4, (me.n_experts, dfe, d), cfg.dtype) * s2,
    }
    if me.n_shared:
        p["shared"] = init_mlp(k5, d, dfe * me.n_shared, cfg.dtype)
    return p


def apply_moe(p, x, cfg: ModelCfg):
    """x: [B, S, d] -> [B, S, d].  Top-k capacity-based routing; overflow
    tokens are dropped.

    Production path (mesh active, E % tp == 0, S % tp == 0): shard_map
    expert parallelism — every device routes its local token shard with a
    sort-based dispatch and exchanges expert buffers with explicit
    ``lax.all_to_all`` over the 'model' axis (exactly the MoE Alltoall
    traffic the paper's collective benchmark models).  Fallback (smoke
    tests, decode steps): dense GShard capacity einsum.
    """
    me = cfg.moe
    B, S, d = x.shape
    ctx = _common._SHARD_CTX
    tp = ctx["mesh"].shape.get(ctx["tp"], 1) if ctx else 1
    if ctx is not None and me.n_experts % tp == 0 and S % tp == 0 and tp > 1:
        out, aux = _apply_moe_ep(p, x, cfg, ctx, tp)
    elif ctx is not None and S % tp == 0 and tp > 1:
        # E < tp (mixtral 8e @ tp=16): f-sharded expert-parallel path
        out, aux = _apply_moe_ep_fshard(p, x, cfg, ctx, tp)
    else:
        out, aux = _apply_moe_dense(p, x, cfg)
    if me.n_shared:
        out = out + apply_mlp(p["shared"], x)
    return out, aux


def _apply_moe_dense(p, x, cfg: ModelCfg):
    """Sort-based capacity dispatch (no all-to-all; experts replicated or
    TP-within-expert via the sharding rules).

    Perf note (EXPERIMENTS.md §Perf, mixtral hillclimb): the original
    GShard einsum dispatch materializes a [T, E, C] one-hot tensor whose
    dispatch/combine einsums cost O(T^2) FLOPs (C ∝ T) — 2.8e17 FLOPs/chip
    for mixtral train_4k.  The sort-based path is O(T log T + active-expert
    matmuls), identical output (same capacity rule, first-come-first-kept
    in token order), validated against the einsum oracle in tests."""
    me = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    cap = int(max(1, me.capacity_factor * me.top_k * T / me.n_experts))
    buf, dst, keep, gate, counts = _local_dispatch(
        xt, probs, me.top_k, cap, me.n_experts)
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])
    flat = ye.reshape(me.n_experts * cap, d)
    ys = flat[jnp.minimum(dst, me.n_experts * cap - 1)] * \
        keep[:, None].astype(flat.dtype)
    gk = (gate * keep).reshape(T, me.top_k)
    yk = ys.reshape(T, me.top_k, d)
    denom = jnp.maximum(gk.sum(1, keepdims=True), 1e-9)
    out = jnp.einsum("tkd,tk->td", yk, (gk / denom).astype(yk.dtype))
    me_frac = jnp.mean(probs, axis=0)
    ce_frac = counts.astype(jnp.float32) / jnp.maximum(
        keep.sum().astype(jnp.float32), 1.0)
    aux = me.n_experts * jnp.sum(me_frac * ce_frac)
    return out.reshape(B, S, d).astype(x.dtype), aux


def _apply_moe_dense_einsum(p, x, cfg: ModelCfg):
    """GShard one-hot einsum dispatch — kept as the small-shape oracle for
    tests (O(T^2); do not use at scale)."""
    me = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    cap = int(max(1, me.capacity_factor * me.top_k * T / me.n_experts))
    gates, dispatch = _topk_capacity(probs, me.top_k, cap)
    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xt)
    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])
    out = jnp.einsum("tec,ecd->td", gates.astype(x.dtype), ye)
    me_frac = jnp.mean(probs, axis=0)
    ce_frac = jnp.mean(dispatch.sum(-1).astype(jnp.float32), axis=0)
    aux = me.n_experts * jnp.sum(me_frac * ce_frac)
    return out.reshape(B, S, d), aux


def _local_dispatch(xt, probs, top_k: int, cap: int, n_exp: int):
    """Per-device sort-based dispatch: tokens -> [E, cap, d] buffers.

    Returns (buffers, dst, keep, gates, counts)."""
    t, d = xt.shape
    topv, topi = jax.lax.top_k(probs, top_k)          # [t, k]
    slot_e = topi.reshape(-1)
    slot_t = jnp.repeat(jnp.arange(t), top_k)
    gate = topv.reshape(-1)

    order = jnp.argsort(slot_e)
    sorted_e = slot_e[order]
    pos = jnp.arange(t * top_k)
    is_start = jnp.concatenate([jnp.ones(1, bool),
                                sorted_e[1:] != sorted_e[:-1]])
    seg_start = jax.lax.associative_scan(jnp.maximum,
                                         jnp.where(is_start, pos, 0))
    rank_sorted = pos - seg_start
    rank = jnp.zeros(t * top_k, jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    keep = rank < cap
    dst = jnp.where(keep, slot_e * cap + rank, n_exp * cap)
    buf = jnp.zeros((n_exp * cap + 1, d), xt.dtype).at[dst].add(xt[slot_t])
    counts = jnp.zeros(n_exp + 1, jnp.int32).at[
        jnp.where(keep, slot_e, n_exp)].add(1)[:n_exp]
    return buf[:-1].reshape(n_exp, cap, d), dst, keep, gate, counts


def _apply_moe_ep(p, x, cfg: ModelCfg, ctx, tp: int):
    me = cfg.moe
    B, S, d = x.shape
    from jax.sharding import PartitionSpec as P
    dp = ctx["dp"]
    tpa = ctx["tp"]
    mesh = ctx["mesh"]
    E = me.n_experts

    def local(xl, router, wg, wu, wd):
        # xl: [B_loc, S/tp, d] local tokens; wg/wu/wd: [E/tp, d, f] local experts
        bl, sl, _ = xl.shape
        t = bl * sl
        xt = xl.reshape(t, d)
        probs = jax.nn.softmax(
            jnp.einsum("td,de->te", xt.astype(jnp.float32), router), -1)
        cap = int(max(1, me.capacity_factor * me.top_k * t / E))
        buf, dst, keep, gate, counts = _local_dispatch(
            xt, probs, me.top_k, cap, E)
        # exchange: experts scatter over 'model', token-chunks gather
        recv = jax.lax.all_to_all(buf, tpa, split_axis=0, concat_axis=1,
                                  tiled=True)          # [E/tp, cap*tp, d]
        g = jnp.einsum("ecd,edf->ecf", recv, wg)
        u = jnp.einsum("ecd,edf->ecf", recv, wu)
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)
        back = jax.lax.all_to_all(y, tpa, split_axis=1, concat_axis=0,
                                  tiled=True)          # [E, cap, d]
        flat = back.reshape(E * cap, d)
        ys = flat[jnp.minimum(dst, E * cap - 1)] * keep[:, None]
        gk = (gate * keep).reshape(t, me.top_k)
        yk = ys.reshape(t, me.top_k, d)
        denom = jnp.maximum(gk.sum(1, keepdims=True), 1e-9)
        out = jnp.einsum("tkd,tk->td", yk, gk / denom).astype(xl.dtype)
        # Switch-style load-balance aux (local estimate, averaged below)
        me_frac = jnp.mean(probs, axis=0)
        ce_frac = counts.astype(jnp.float32) / jnp.maximum(
            keep.sum().astype(jnp.float32), 1.0)
        aux = E * jnp.sum(me_frac * ce_frac)
        aux = jax.lax.pmean(aux, tpa)
        for ax in dp:
            aux = jax.lax.pmean(aux, ax)
        return out.reshape(bl, sl, d), aux

    out, aux = shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, tpa, None), P(None, None),
                  P(tpa, None, None), P(tpa, None, None), P(tpa, None, None)),
        out_specs=(P(dp, tpa, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, aux


def _apply_moe_ep_fshard(p, x, cfg: ModelCfg, ctx, tp: int):
    """Expert parallelism when n_experts doesn't divide tp (mixtral 8e @
    tp=16): expert FFN dims stay f-sharded (the existing param layout), but
    dispatch/combine run *inside* shard_map so GSPMD can't replicate the
    data-dependent scatters.

    Perf (EXPERIMENTS.md §Perf, mixtral iteration 2): the GSPMD-partitioned
    dense path lowers the [E,cap,d] partial-sum contractions to per-layer
    all-reduces (~1e13 B/chip/step).  Here each device (a) sort-dispatches
    its own T/tp tokens, (b) all-gathers the compact [E,cap_l,d] buffers,
    (c) computes every expert on its f/tp weight slice, (d) psum_scatters
    the partial outputs back to token owners — AG+RS volume is ~20x less
    than the all-reduce chain, and flops stay balanced (full capacity x
    f/tp per device)."""
    me = cfg.moe
    B, S, d = x.shape
    from jax.sharding import PartitionSpec as P
    dp, tpa, mesh = ctx["dp"], ctx["tp"], ctx["mesh"]
    E = me.n_experts

    def local(xl, router, wg, wu, wd):
        # xl: [B_loc, S/tp, d]; wg/wu: [E, d, f/tp]; wd: [E, f/tp, d]
        bl, sl, _ = xl.shape
        t = bl * sl
        xt = xl.reshape(t, d)
        probs = jax.nn.softmax(
            jnp.einsum("td,de->te", xt.astype(jnp.float32), router), -1)
        cap = int(max(1, me.capacity_factor * me.top_k * t / E))
        buf, dst, keep, gate, counts = _local_dispatch(
            xt, probs, me.top_k, cap, E)
        bufs = jax.lax.all_gather(buf, tpa)           # [tp, E, cap, d]
        g = jnp.einsum("pecd,edf->pecf", bufs, wg)
        u = jnp.einsum("pecd,edf->pecf", bufs, wu)
        y = jnp.einsum("pecf,efd->pecd", jax.nn.silu(g) * u, wd)
        # sum the f-shard partials AND return each sender its own slot
        y = jax.lax.psum_scatter(y, tpa, scatter_dimension=0, tiled=False)
        flat = y.reshape(E * cap, d)                  # [E, cap, d] summed
        ys = flat[jnp.minimum(dst, E * cap - 1)] * keep[:, None]
        gk = (gate * keep).reshape(t, me.top_k)
        yk = ys.reshape(t, me.top_k, d)
        denom = jnp.maximum(gk.sum(1, keepdims=True), 1e-9)
        out = jnp.einsum("tkd,tk->td", yk, gk / denom).astype(xl.dtype)
        me_frac = jnp.mean(probs, axis=0)
        ce_frac = counts.astype(jnp.float32) / jnp.maximum(
            keep.sum().astype(jnp.float32), 1.0)
        aux = E * jnp.sum(me_frac * ce_frac)
        aux = jax.lax.pmean(aux, tpa)
        for ax in dp:
            aux = jax.lax.pmean(aux, ax)
        return out.reshape(bl, sl, d), aux

    out, aux = shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, tpa, None), P(None, None),
                  P(None, None, tpa), P(None, None, tpa), P(None, tpa, None)),
        out_specs=(P(dp, tpa, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, aux


def _topk_capacity(probs, top_k: int, cap: int):
    """probs [T, E] -> (gates [T,E,C], dispatch [T,E,C])."""
    T, E = probs.shape
    topv, topi = jax.lax.top_k(probs, top_k)           # [T, k]
    # one-hot expert assignment per slot
    assign = jax.nn.one_hot(topi, E, dtype=jnp.int32)  # [T, k, E]
    # position of each (token, slot) within its expert queue
    flat = assign.reshape(T * top_k, E)
    pos_in_e = jnp.cumsum(flat, axis=0) * flat - 1     # [T*k, E]
    keep = (pos_in_e < cap) & (pos_in_e >= 0)
    pos = jnp.clip(pos_in_e, 0, cap - 1)
    capslot = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
    disp = capslot.reshape(T, top_k, E, cap).sum(axis=1)       # [T, E, C]
    gate_vals = (topv[..., None] * jnp.ones((1, 1, E))) * assign  # [T,k,E]
    gates = jnp.einsum("tke,tkec->tec",
                       gate_vals,
                       capslot.reshape(T, top_k, E, cap))
    # renormalize kept top-k gates
    gsum = gates.sum(axis=(1, 2), keepdims=True)
    gates = gates / jnp.maximum(gsum, 1e-9)
    return gates, disp
