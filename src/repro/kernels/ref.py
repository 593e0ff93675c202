"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.red_ecn import mark_scale
from repro.net.policies.base import prefix_sum


def mha_reference(q, k, v, *, causal: bool = True, sliding_window: int = 0,
                  q_offset: int = 0):
    """q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D] (GQA) -> [B, Sq, Hq, D]."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    s = s / np.sqrt(D)
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if sliding_window:
        mask &= kpos[None, :] > qpos[:, None] - sliding_window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)


def spritz_select_reference(w, u, buf_front, packet_count, *,
                            explore_threshold: int):
    """Mirror of repro.core.spritz.send_logic's selection core."""
    w = w.astype(jnp.float32)
    csum = prefix_sum(w)
    total = csum[:, -1]
    uu = u * jnp.maximum(total, 1e-30)
    sampled = jnp.minimum(
        jnp.sum((csum < uu[:, None]).astype(jnp.int32), axis=1),
        w.shape[1] - 1)
    explore = packet_count >= explore_threshold
    use_buffer = (~explore) & (buf_front >= 0)
    ev = jnp.where(use_buffer, buf_front, sampled)
    new_count = jnp.where(explore, 0, packet_count + 1)
    return ev, new_count, use_buffer


def red_ecn_reference(eport, rank, enq, unif, q_tail, t, *, qsize, kmin,
                      kmax, n_ports):
    """Oracle for kernels.red_ecn (mirrors engine.py section E)."""
    port_c = jnp.minimum(eport, n_ports - 1)
    tail = q_tail[port_c]
    occ = jnp.maximum(tail - t, 0) + rank
    trim = enq & (occ >= qsize)
    accept = enq & ~trim
    pr = jnp.clip((occ.astype(jnp.float32) - kmin) * mark_scale(kmin, kmax),
                  0.0, 1.0)
    mark = accept & (unif < pr)
    slot = jnp.maximum(tail, t) + rank + 1
    return occ, trim, mark, jnp.where(accept, slot, 0)


def tick_rank_reference(port, *, n_ports: int):
    """Oracle for kernels.tick_rank: position among equal port values,
    ordered by index (a stable segmented rank).  Entries outside
    ``[0, n_ports)`` share one overflow bucket (engine callers mask
    them out)."""
    port_c = jnp.where((port < 0) | (port >= n_ports), n_ports, port)
    oh = port_c[:, None] == jnp.arange(n_ports + 1, dtype=jnp.int32)[None, :]
    pos = jnp.cumsum(oh.astype(jnp.int32), axis=0) * oh
    return jnp.maximum(pos.sum(-1) - 1, 0).astype(jnp.int32)


def flow_agg_reference(rows, pflow, *, n_flows: int):
    """Oracle for kernels.flow_agg: ``out[k, f] = sum(rows[k, pflow ==
    f])`` as an integer segment sum (exact on every backend)."""
    pf = jnp.where((pflow < 0) | (pflow >= n_flows), n_flows, pflow)
    return jnp.zeros((n_flows + 1, rows.shape[0]), jnp.int32).at[pf].add(
        rows.T.astype(jnp.int32))[:n_flows].T


def rwkv6_reference(r, k, v, w, u, wkv0):
    """Sequential RWKV-6 recurrence (fp32).

    r,k,v,w: [B, S, H, hd]; u: [H, hd]; wkv0: [B, H, hd, hd].
    Returns (y [B,S,H,hd], wkv_final)."""
    B, S, H, hd = r.shape
    def step(wkv, inp):
        rt, kt, vt, wt = inp
        att = wkv + u[None, :, :, None] * (kt[..., None] * vt[..., None, :])
        yt = jnp.einsum("bhk,bhkv->bhv", rt, att)
        wkv = wt[..., None] * wkv + kt[..., None] * vt[..., None, :]
        return wkv, yt
    xs = tuple(a.transpose(1, 0, 2, 3).astype(jnp.float32) for a in (r, k, v, w))
    wkv, ys = jax.lax.scan(step, wkv0.astype(jnp.float32), xs)
    return ys.transpose(1, 0, 2, 3), wkv
