"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to False on a TPU backend — the kernels are written
for TPU BlockSpec tiling and always compile through Mosaic there — and to
True elsewhere, where the interpreter runs the kernel bodies so the CPU
tests can check them against the ``ref`` oracles.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.spritz_select import spritz_select as _select
from repro.kernels.rwkv6_chunked import rwkv6_chunked as _rwkv6


def _default_interpret() -> bool:
    """Interpret mode exactly when the default backend is not a TPU."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, sliding_window=0, q_offset=0,
                    block_q=128, block_k=128, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _flash(q, k, v, causal=causal, sliding_window=sliding_window,
                  q_offset=q_offset, block_q=block_q, block_k=block_k,
                  interpret=interpret)


def spritz_select(w, u, buf_front, packet_count, *, explore_threshold,
                  block_f=256, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _select(w, u, buf_front, packet_count,
                   explore_threshold=explore_threshold, block_f=block_f,
                   interpret=interpret)


def rwkv6_chunked(r, k, v, w, u, wkv0, *, chunk=64, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _rwkv6(r, k, v, w, u, wkv0, chunk=chunk, interpret=interpret)


def red_ecn(eport, rank, enq, unif, q_tail, t, *, qsize, kmin, kmax,
            n_ports, block_n=1024, interpret=None):
    from repro.kernels.red_ecn import red_ecn as _red
    if interpret is None:
        interpret = _default_interpret()
    return _red(eport, rank, enq, unif, q_tail, t, qsize=qsize, kmin=kmin,
                kmax=kmax, n_ports=n_ports, block_n=block_n,
                interpret=interpret)


def tick_rank(port, *, n_ports, block_m=512, interpret=None):
    from repro.kernels.tick_rank import tick_rank as _rank
    if interpret is None:
        interpret = _default_interpret()
    return _rank(port, n_ports=n_ports, block_m=block_m,
                 interpret=interpret)


def flow_agg(rows, pflow, *, n_flows, block_n=1024, interpret=None):
    from repro.kernels.flow_agg import flow_agg as _agg
    if interpret is None:
        interpret = _default_interpret()
    return _agg(rows, pflow, n_flows=n_flows, block_n=block_n,
                interpret=interpret)
