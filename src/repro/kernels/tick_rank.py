"""Per-tick FIFO enqueue rank as a Pallas TPU kernel.

The packet engine's enqueue phase needs, for every packet enqueued this
tick, its arrival rank among same-tick arrivals at the same egress port
(engine.py ``_enqueue_rank``): the analytic FIFO then departs the rank-k
accept at ``max(tail, t) + k + 1``.  At paper scale the engine's one-hot
rank histogram ([M, n_ports] cells) blows the one-hot budget and the
argsort fallback serializes; this kernel counts, for every entry ``i``,
the earlier entries ``j < i`` with the same port as a pairwise compare
over [block, block] tiles:

    rank[i] = sum_{j < i} (port[j] == port[i])

O(M^2 / 2) exact integer work, independent of ``n_ports`` — no per-port
scratch, no one-hot over ports, no in-kernel scan or gather.

Grid is (i-block, j-block): i-blocks lay the ranked entries along lanes
(output block ``[1, block]``), j-blocks lay the compared entries along
sublanes (input ``[block, 1]``); j is the reduction axis (innermost,
sequential), and tiles with ``j > i`` are skipped.  Per-tile counts are
at most ``block`` (< 2^24), so the f32 sublane reduction is exact.

Entries outside ``[0, n_ports)`` (the compaction sentinel ``n_ports``,
or -1 pads) share one overflow bucket; their ranks are well-defined but
engine callers never consume them (they are masked by ``valid``).
Oracle: ``ref.tick_rank_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _tick_rank_kernel(pj_ref, pi_ref, rank_ref, *, block):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        rank_ref[...] = jnp.zeros_like(rank_ref)

    @pl.when(j <= i)
    def _count():
        pj = pj_ref[...]                                       # [bj, 1]
        pi = pi_ref[...]                                       # [1, bi]
        gj = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)
        gi = i * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1)
        hit = (pj == pi) & (gj < gi)                           # [bj, bi]
        rank_ref[...] += jnp.sum(hit.astype(jnp.float32), axis=0,
                                 keepdims=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_ports", "block_m",
                                             "interpret"))
def tick_rank(port, *, n_ports: int, block_m: int = 512,
              interpret: bool = True):
    """port: [M] i32 egress port per compacted enqueue.  Returns rank [M]
    i32 — position among this tick's enqueues of the same port, ordered
    by index."""
    if port.ndim != 1:
        raise ValueError(f"port must be 1-D, got shape {port.shape}")
    if port.dtype != jnp.int32:
        raise ValueError(f"port must be int32, got {port.dtype}")
    if n_ports < 1:
        raise ValueError(f"n_ports must be >= 1, got {n_ports}")
    M = port.shape[0]
    # lane-aligned tile: block_m rounded up to a multiple of 128
    block = pl.cdiv(min(block_m, M), 128) * 128
    padM = pl.cdiv(M, block) * block
    # out-of-range entries (sentinel n_ports, -1 pads) -> overflow bucket;
    # pads land there *after* every real entry, so real ranks are unchanged
    port_c = jnp.where((port < 0) | (port >= n_ports), n_ports, port)
    port_c = jnp.pad(port_c, (0, padM - M), constant_values=n_ports)
    nb = padM // block
    rank = pl.pallas_call(
        functools.partial(_tick_rank_kernel, block=block),
        grid=(nb, nb),
        in_specs=[pl.BlockSpec((block, 1), lambda i, j: (j, 0)),
                  pl.BlockSpec((1, block), lambda i, j: (0, i))],
        out_specs=pl.BlockSpec((1, block), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, padM), jnp.int32),
        interpret=interpret,
    )(port_c.reshape(padM, 1), port_c.reshape(1, padM))
    return rank[0, :M]
