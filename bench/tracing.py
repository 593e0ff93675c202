"""Reduction of one profiler trace to the benchmark's device numbers.

The traced window runs from the start of the first ``bench.sweep`` host
span to the end of the last.  On each chip, busy time is the union of
the intervals in which a leaf operation of the ``XLA Ops`` line ran;
control-flow operations (a ``while`` spans its whole loop) contain other
operations and are left out, so time between a loop's steps counts as
idle.  Each idle gap is put down to the innermost benchmark span
(``bench.*``) covering its midpoint, refined by the innermost runtime
event on that span's host thread (dispatch, transfer, allocation), or
``host`` where the runtime recorded nothing: Python work on the host.
``program_trace.read_events`` reads the events from the trace's file.
"""
from __future__ import annotations

import heapq
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
_KIND = re.compile(r"kind=(\w+)")


def short_name(hlo: str) -> str:
    """``%fusion.7 = s32[..] fusion(..), kind=kLoop`` -> ``fusion.7 fusion kLoop``."""
    name, _, rest = hlo.partition(" = ")
    parts = [name.lstrip("%")]
    m = _OPCODE.search(rest)
    if m:
        parts.append(m.group(1))
    k = _KIND.search(rest)
    if k:
        parts.append(k.group(1))
    return " ".join(parts)


def leaves(ops):
    """The operations that contain no other operation of the same line."""
    ops = sorted(ops, key=lambda o: (o[0], -(o[1] - o[0])))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[0] >= o[1]]


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that ``busy`` (merged) leaves uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def innermost(events, times) -> list:
    """For each of ``times``, the name of the shortest event (start, end,
    name) whose closed interval holds it, the smaller name on a tie, or
    None: one pass over the times in order, with the events that have
    started on a heap keyed by (duration, name)."""
    evs = sorted(events)
    out: list = [None] * len(times)
    heap: list = []
    i = 0
    for k in sorted(range(len(times)), key=times.__getitem__):
        t = times[k]
        while i < len(evs) and evs[i][0] <= t:
            s, e, n = evs[i]
            heapq.heappush(heap, (e - s, n, e))
            i += 1
        while heap and heap[0][2] < t:
            heapq.heappop(heap)
        if heap:
            out[k] = heap[0][1]
    return out


def reduce_events(host_spans, device_ops, n_devices: int,
                  host_events=()) -> dict:
    """``host_spans``: (start_ns, end_ns, name) of the benchmark's spans;
    ``device_ops``: {chip: [(start_ns, end_ns, op name), ...]};
    ``host_events``: runtime events of the spans' host thread."""
    sweeps = [(s, e) for s, e, n in host_spans if n == "bench.sweep"]
    if not sweeps:
        raise ValueError("the trace holds no bench.sweep span")
    lo, hi = min(s for s, _ in sweeps), max(e for _, e in sweeps)
    chips = sorted(device_ops)[:n_devices]
    if len(chips) < n_devices:
        raise ValueError(f"the trace holds {len(chips)} chips, not {n_devices}")
    busy, op_time, idle = {}, defaultdict(float), []
    for c in chips:
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in leaves(device_ops[c])
               if e > lo and s < hi]
        merged = union((s, e) for s, e, _ in ops)
        busy[c] = sum(e - s for s, e in merged) * 1e-9
        for s, e, n in ops:
            op_time[short_name(n)] += (e - s) * 1e-9
        idle += [(s, e, c) for s, e in gaps(merged, lo, hi)]
    mids = [(s + e) / 2 for s, e, _ in idle]
    gap_list = [((e - s) * 1e-9, f"{span or 'outside_bench_spans'}:"
                 f"{what or 'host'}@chip{c}")
                for (s, e, c), span, what in zip(
                    idle, innermost(host_spans, mids),
                    innermost(host_events, mids))]
    window_s = (hi - lo) * 1e-9
    gap_list.sort(reverse=True)
    return {
        "window_s": window_s,
        "busy_s": sum(busy.values()) / len(chips),
        "busy_per_chip_s": [busy[c] for c in chips],
        "idle_frac_per_chip": [1.0 - busy[c] / window_s for c in chips],
        "breakdown": {
            "device_ops": [[n, t] for n, t in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[label, t] for t, label in gap_list[:TOP]],
        },
    }
