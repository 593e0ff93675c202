"""The ``websearch`` traffic pattern: open-loop Poisson arrivals with flow
sizes drawn from a stated size distribution, the Spritz paper's
datacenter workload (§V-B).  Written from the published recipe; the
size law is data in the mix, so another distribution is another mix.

A mix gives:

- ``cdf``: (bytes, cumulative share) points of the flow-size law,
  interpolated piecewise-linearly, with ``cdf_source`` naming them;
- ``load``: packets offered per endpoint per tick, as a share of line
  rate (one packet per tick);
- ``arrival_ticks``: every flow starts in ``[0, arrival_ticks)``;
- ``max_senders_per_recv``: the paper's cap on the flows a receiver
  has in progress at once;
- ``seed``; optionally ``size_cap_pkts`` (a cut: a larger flow is sent
  at that size).

A flow of ``b`` bytes is ``ceil(b / payload)`` packets, the payload being
the configuration's ``packet_bytes`` less a ``HEADER_BYTES`` header.
Endpoint ``e`` draws from a stream of its own, seeded ``(seed, e)``: a
Poisson count of arrivals at ``load / E[packets]`` per tick over the
window, their times (uniform over the window, whole ticks), their sizes,
and ``CANDIDATES`` receivers per flow, uniform over the other endpoints.
Flows are placed in (start, src) order and each goes to its first
candidate with fewer than ``max_senders_per_recv`` flows in progress, a
flow being in progress at tick ``t`` while ``t < start + size +
bdp_packets`` (its serialization at line rate and one round trip);
where every candidate is full it goes to the least busy receiver (the
lowest index among equals).
No flow is dropped, so the offered load is kept.  Flows come ordered by
(start, src, dst).
"""
from __future__ import annotations

import heapq

import numpy as np

HEADER_BYTES = 64      # the configurations' 4,096 B payload + 64 B header
CANDIDATES = 8         # receivers drawn per flow


def size_law(mix: dict) -> tuple[np.ndarray, np.ndarray]:
    """(bytes, cumulative share) of the mix's ``cdf``, checked."""
    pts = np.asarray(mix["cdf"], np.float64)
    xs, cs = pts[:, 0], pts[:, 1]
    if (len(xs) < 2 or cs[0] != 0.0 or cs[-1] != 1.0 or xs[0] < 0
            or np.any(np.diff(xs) < 0) or np.any(np.diff(cs) < 0)):
        raise ValueError("cdf: (bytes, share) points with bytes and share "
                         "non-decreasing, share from 0 to 1")
    return xs, cs


def packets(nbytes, payload: int, cap: int | None = None) -> np.ndarray:
    """Packets of flows of ``nbytes`` bytes (at least one), at most ``cap``."""
    n = np.maximum(1, np.ceil(np.asarray(nbytes, np.float64) / payload))
    return (n if cap is None else np.minimum(n, cap)).astype(np.int64)


def mean_packets(xs, cs, payload: int, cap: int | None = None) -> float:
    """E[packets] under the piecewise-linear law, exactly: bytes are
    uniform inside each segment, and a segment's mean of the step
    function ``packets`` is its integral over the segment's width."""
    c = np.inf if cap is None else cap

    def integral(x):   # of min(max(1, ceil(t / payload)), cap) over [0, x]
        if x > c * payload:
            return integral(c * payload) + c * (x - c * payload)
        k = max(1, int(np.ceil(x / payload)))
        return payload * k * (k - 1) / 2 + k * (x - (k - 1) * payload)

    total = 0.0
    for x0, x1, mass in zip(xs[:-1], xs[1:], np.diff(cs)):
        if mass == 0:
            continue
        if x1 > x0:
            total += mass * (integral(x1) - integral(x0)) / (x1 - x0)
        else:
            total += mass * float(packets(x0, payload, cap))
    return total


def flows(mix: dict, group: np.ndarray, config: dict) -> dict:
    """Open-loop flows over ``len(group)`` endpoints (``group`` is not
    read otherwise); ``config`` gives ``packet_bytes`` and ``bdp_packets``."""
    n = len(group)
    if n < 2:
        raise ValueError("websearch traffic needs two endpoints or more")
    xs, cs = size_law(mix)
    payload = int(config["wire"]["packet_bytes"]) - HEADER_BYTES
    cap = mix.get("size_cap_pkts")
    window = int(mix["arrival_ticks"])
    rate = float(mix["load"]) / mean_packets(xs, cs, payload, cap)
    parts = []
    for e in range(n):
        rng = np.random.default_rng([int(mix["seed"]), e])
        k = int(rng.poisson(rate * window))
        start = np.floor(np.sort(rng.uniform(0, window, k))).astype(np.int64)
        size = packets(np.interp(rng.random(k), cs, xs), payload, cap)
        cand = rng.integers(0, n - 1, (k, CANDIDATES))
        parts.append((np.full(k, e), start, size, cand + (cand >= e)))
    src, start, size, cand = (np.concatenate(a) for a in zip(*parts))
    if not len(src):
        raise ValueError("no flow arrives in the window: raise load or "
                         "arrival_ticks")
    order = np.lexsort((src, start))     # stable: each source's draw order
    src, start, size, cand = src[order], start[order], size[order], cand[order]
    dst = _cap_senders(src, start, size, cand, n,
                       int(mix["max_senders_per_recv"]),
                       int(config["bdp_packets"]))
    order = np.lexsort((dst, src, start))
    return {"src": src[order].astype(np.int32),
            "dst": dst[order].astype(np.int32),
            "size": size[order].astype(np.int32),
            "start": start[order].astype(np.int32)}


def _cap_senders(src, start, size, cand, n: int, limit: int,
                 rtt: int) -> np.ndarray:
    """Receivers for flows in (start, src) order, each with fewer than
    ``limit`` flows in progress where a candidate allows, else the least
    busy."""
    ends: list[list[int]] = [[] for _ in range(n)]   # heaps of end ticks

    def busy(d: int, t: int) -> int:
        h = ends[d]
        while h and h[0] <= t:
            heapq.heappop(h)
        return len(h)

    dst = np.empty(len(src), np.int64)
    for i, (s, t, c) in enumerate(zip(src.tolist(), start.tolist(),
                                      cand.tolist())):
        d = next((d for d in c if busy(d, t) < limit), None)
        if d is None:
            d = min((busy(r, t), r) for r in range(n) if r != s)[1]
        heapq.heappush(ends[d], t + int(size[i]) + rtt)
        dst[i] = d
    return dst
