"""RED/ECN enqueue stage as a Pallas TPU kernel.

The packet simulator's per-tick enqueue (engine.py section E): for every
compacted enqueue, given the target port's service tail, its FIFO rank
among same-tick arrivals and the tick, compute

    occupancy  = max(tail[port] - t, 0) + rank
    trim       = enqueue & (occupancy >= qsize)
    mark_prob  = clip((occupancy - kmin) * 1/(kmax - kmin), 0, 1)
    mark       = accept & (uniform < mark_prob)
    slot       = max(tail[port], t) + rank + 1

The per-port tail gather (``tail[port]``) and the tick offsets run in the
jnp wrapper; the kernel is a purely elementwise VPU pass over lane-dense
[rows, 128] tiles.  The marking probability multiplies by the reciprocal
span (a trace-time constant) rather than dividing, so the kernel, the
engine's jnp path and the oracle round identically on every backend.
Boolean inputs/outputs travel as int32.  Oracle: ``ref.red_ecn_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
_SUBLANES = 8


def mark_scale(kmin: float, kmax: float) -> float:
    """Reciprocal of the RED span — the one constant every RED formulation
    multiplies by (kernel, engine jnp path, oracle)."""
    return 1.0 / max(kmax - kmin, 1e-9)


def _red_ecn_kernel(backlog_ref, base_ref, rank_ref, enq_ref, unif_ref,
                    occ_ref, trim_ref, mark_ref, slot_ref,
                    *, qsize, kmin, scale):
    rank = rank_ref[...]
    enq = enq_ref[...] != 0
    occ = backlog_ref[...] + rank
    trim = enq & (occ >= qsize)
    accept = enq & ~trim
    pr = jnp.clip((occ.astype(jnp.float32) - kmin) * scale, 0.0, 1.0)
    mark = accept & (unif_ref[...] < pr)

    occ_ref[...] = occ
    trim_ref[...] = trim.astype(jnp.int32)
    mark_ref[...] = mark.astype(jnp.int32)
    slot_ref[...] = jnp.where(accept, base_ref[...] + rank + 1, 0)


@functools.partial(jax.jit, static_argnames=("qsize", "kmin", "kmax",
                                             "n_ports", "block_n",
                                             "interpret"))
def red_ecn(eport, rank, enq, unif, q_tail, t, *, qsize: int, kmin: float,
            kmax: float, n_ports: int, block_n: int = 1024,
            interpret: bool = True):
    """eport/rank: [N] i32; enq: [N] bool; unif: [N] f32; q_tail: [P] i32.

    Returns (occ [N] i32, trim [N] bool, mark [N] bool, slot [N] i32)."""
    if not (eport.ndim == rank.ndim == enq.ndim == unif.ndim == 1):
        raise ValueError("eport/rank/enq/unif must be 1-D")
    if not (eport.shape == rank.shape == enq.shape == unif.shape):
        raise ValueError(
            f"ragged inputs: eport {eport.shape}, rank {rank.shape}, "
            f"enq {enq.shape}, unif {unif.shape}")
    if eport.dtype != jnp.int32 or rank.dtype != jnp.int32:
        raise ValueError(
            f"eport/rank must be int32, got {eport.dtype}/{rank.dtype}")
    if q_tail.shape != (n_ports,):
        raise ValueError(
            f"q_tail shape {q_tail.shape} != (n_ports,) = ({n_ports},)")
    N = eport.shape[0]
    # [rows, 128] tiles of a multiple of 8 rows: block_n rounded up to
    # whole (8, 128) vregs, and no larger than the padded input
    rows = pl.cdiv(N, _LANES)
    br = pl.cdiv(min(pl.cdiv(block_n, _LANES), rows), _SUBLANES) * _SUBLANES
    padR = pl.cdiv(rows, br) * br
    padN = padR * _LANES

    t = jnp.asarray(t, jnp.int32)
    tail = q_tail[jnp.minimum(eport, n_ports - 1)]

    def tile(x):
        # pads carry enq=0: occ/slot garbage is masked and sliced off
        return jnp.pad(x, (0, padN - N)).reshape(padR, _LANES)

    spec = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    kern = functools.partial(_red_ecn_kernel, qsize=qsize, kmin=kmin,
                             scale=mark_scale(kmin, kmax))
    occ, trim, mark, slot = pl.pallas_call(
        kern,
        grid=(padR // br,),
        in_specs=[spec] * 5,
        out_specs=[spec] * 4,
        out_shape=[jax.ShapeDtypeStruct((padR, _LANES), jnp.int32)] * 4,
        interpret=interpret,
    )(tile(jnp.maximum(tail - t, 0)), tile(jnp.maximum(tail, t)),
      tile(rank), tile(enq.astype(jnp.int32)), tile(unif.astype(jnp.float32)))

    def flat(x):
        return x.reshape(padN)[:N]

    return (flat(occ), flat(trim).astype(bool), flat(mark).astype(bool),
            flat(slot))
