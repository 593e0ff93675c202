"""Per-flow aggregation (one-hot GEMM) as a Pallas TPU kernel.

The packet engine folds K per-packet indicator/value rows into per-flow
sums every tick (feedback counts, delivery PSNs — engine.py
``flow_sums_fn``).  The jnp fast path materializes the full [N, F]
one-hot operand for one GEMM, which blows the one-hot cell budget at
paper scale (N x F ~ 3.6e7 for DF-1056); the scatter fallback walks
updates serially on CPU.  This kernel streams the packet table in blocks
and accumulates ``rows_block @ onehot_block`` into the [K, F] output —
the same MXU-friendly GEMM, without ever materializing [N, F].

Grid is 1-D over packet blocks, executed sequentially; the output block
maps every iteration to the same [K, F] tile, zero-initialized at block 0
and accumulated in f32.  The product runs at ``Precision.HIGHEST``: the
TPU's default precision multiplies in bf16, which holds integers exactly
only up to 256, while a delivered PSN can exceed that.  At full f32
precision every integer value below 2^24 times a 0/1 one-hot is exact,
and so is the f32 accumulation of sums below 2^24; the result is cast
back to int32.  The flow id travels as a [N, 1] column so the one-hot is
built with packets on sublanes and flows on lanes; the flow axis is
padded to a multiple of 128.  Oracle: ``ref.flow_agg_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _flow_agg_kernel(rows_ref, pflow_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = rows_ref[...].astype(jnp.float32)                   # [K, bn]
    pf = pflow_ref[...]                                        # [bn, 1]
    oh = (pf == jax.lax.broadcasted_iota(
        jnp.int32, (pf.shape[0], out_ref.shape[1]), 1)
          ).astype(jnp.float32)                                # [bn, Fp]
    out_ref[...] += jnp.dot(rows, oh, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_flows", "block_n",
                                             "interpret"))
def flow_agg(rows, pflow, *, n_flows: int, block_n: int = 1024,
             interpret: bool = True):
    """rows: [K, N] integer-valued; pflow: [N] i32 flow id per packet slot.
    Returns [K, n_flows] i32: ``out[k, f] = sum(rows[k, pflow == f])``.
    Entries with ``pflow`` outside [0, n_flows) contribute nowhere."""
    if rows.ndim != 2:
        raise ValueError(f"rows must be 2-D [K, N], got shape {rows.shape}")
    if pflow.ndim != 1:
        raise ValueError(f"pflow must be 1-D, got shape {pflow.shape}")
    if rows.shape[1] != pflow.shape[0]:
        raise ValueError(
            f"rows/pflow length mismatch: {rows.shape[1]} vs "
            f"{pflow.shape[0]}")
    if pflow.dtype != jnp.int32:
        raise ValueError(f"pflow must be int32, got {pflow.dtype}")
    if n_flows < 1:
        raise ValueError(f"n_flows must be >= 1, got {n_flows}")
    K, N = rows.shape
    # lane-aligned tiles: block_n and the flow axis rounded up to 128
    block_n = pl.cdiv(min(block_n, N), 128) * 128
    padN = pl.cdiv(N, block_n) * block_n
    padF = pl.cdiv(n_flows, 128) * 128
    # out-of-range ids (the trash flow n_flows, pads) -> a column that the
    # padded one-hot never has, or one that is sliced off below
    pflow = jnp.where((pflow < 0) | (pflow >= n_flows), padF, pflow)
    rows = jnp.pad(rows.astype(jnp.int32), ((0, 0), (0, padN - N)))
    pflow = jnp.pad(pflow, (0, padN - N), constant_values=padF)
    out = pl.pallas_call(
        _flow_agg_kernel,
        grid=(padN // block_n,),
        in_specs=[
            pl.BlockSpec((K, block_n), lambda i: (0, i)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((K, padF), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, padF), jnp.float32),
        interpret=interpret,
    )(rows, pflow.reshape(padN, 1))
    return out[:, :n_flows].astype(jnp.int32)
