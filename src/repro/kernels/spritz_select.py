"""Spritz send-logic hot loop as a Pallas TPU kernel.

Per packet tick, every active flow runs Algorithm 1: weighted sampling over
its path-weight row (prefix sum + threshold count) fused with the
explore-counter and buffer-front selection.  At datacenter scale this runs
per endpoint per ~80 ns packet slot, so the simulator treats it as its
perf-critical inner kernel (the analogue of the paper's NIC/host datapath).

Layout: the wrapper transposes the weights to [P, F] so flows lie along
lanes and paths along sublanes; the prefix sum is a static unrolled scan
over the P rows (``csum_j = csum_{j-1} + w_j``) — the same f32 add order
as ``policies.base.prefix_sum``, which the pure-jnp sampler uses, so the
kernel and the jnp path draw bit-identical paths on every backend.  Per-
flow vectors travel as lane-dense [1, F] rows; the boolean output is an
int32 row, cast back in the wrapper.  Validated against
``ref.spritz_select_reference`` in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _select_kernel(w_ref, u_ref, front_ref, count_ref, ev_ref, newcnt_ref,
                   used_buf_ref, *, explore_threshold, n_paths):
    csum = [w_ref[0:1, :]]                                     # [1, bf] each
    for p in range(1, n_paths):
        csum.append(csum[-1] + w_ref[p:p + 1, :])
    u = u_ref[...] * jnp.maximum(csum[-1], 1e-30)
    sampled = jnp.zeros(u.shape, jnp.int32)
    for c in csum:
        sampled += (c < u).astype(jnp.int32)
    sampled = jnp.minimum(sampled, n_paths - 1)

    count = count_ref[...]
    front = front_ref[...]
    explore = count >= explore_threshold
    use_buffer = (~explore) & (front >= 0)
    ev_ref[...] = jnp.where(use_buffer, front, sampled)
    newcnt_ref[...] = jnp.where(explore, 0, count + 1)
    used_buf_ref[...] = use_buffer.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("explore_threshold", "block_f",
                                             "interpret"))
def spritz_select(w, u, buf_front, packet_count, *, explore_threshold: int,
                  block_f: int = 256, interpret: bool = True):
    """Batched Algorithm-1 path choice.

    w: [F, P] effective weights; u: [F] uniforms; buf_front: [F] (-1 empty);
    packet_count: [F].  Returns (ev [F], new_count [F], used_buffer [F]).
    """
    if w.ndim != 2:
        raise ValueError(f"w must be 2-D [F, P], got shape {w.shape}")
    if not (u.ndim == buf_front.ndim == packet_count.ndim == 1):
        raise ValueError("u/buf_front/packet_count must be 1-D")
    F, P = w.shape
    if not (u.shape[0] == buf_front.shape[0] == packet_count.shape[0] == F):
        raise ValueError(
            f"ragged inputs: w rows {F}, u {u.shape[0]}, "
            f"buf_front {buf_front.shape[0]}, "
            f"packet_count {packet_count.shape[0]}")
    if buf_front.dtype != jnp.int32 or packet_count.dtype != jnp.int32:
        raise ValueError(
            f"buf_front/packet_count must be int32, got "
            f"{buf_front.dtype}/{packet_count.dtype}")
    # lane-aligned tile: block_f rounded up to a multiple of 128
    block_f = pl.cdiv(min(block_f, F), 128) * 128
    padF = pl.cdiv(F, block_f) * block_f

    def row(x, fill=0):
        return jnp.pad(x, (0, padF - F), constant_values=fill)[None, :]

    wt = jnp.pad(w.astype(jnp.float32), ((0, padF - F), (0, 0))).T  # [P, padF]
    lane = pl.BlockSpec((1, block_f), lambda i: (0, i))
    ev, newcnt, used = pl.pallas_call(
        functools.partial(_select_kernel, explore_threshold=explore_threshold,
                          n_paths=P),
        grid=(padF // block_f,),
        in_specs=[pl.BlockSpec((P, block_f), lambda i: (0, i)),
                  lane, lane, lane],
        out_specs=[lane, lane, lane],
        out_shape=[jax.ShapeDtypeStruct((1, padF), jnp.int32)] * 3,
        interpret=interpret,
    )(wt, row(u.astype(jnp.float32)), row(buf_front, -1), row(packet_count))
    return ev[0, :F], newcnt[0, :F], used[0, :F].astype(bool)
