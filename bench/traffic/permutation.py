"""The ``permutation`` traffic pattern, the Spritz paper's
microbenchmark: a random one-to-one permutation over all endpoints,
receivers outside the sender's group, every flow of ``size_pkts``
packets starting at ``start_tick``.  Each round shuffles and repairs
invalid positions by randomized swaps; if 200 rounds fail it takes the
first valid cyclic shift, and it raises where no valid assignment
exists.
"""
from __future__ import annotations

import numpy as np


def _permutation(group: np.ndarray, seed: int, off_group: bool) -> list[int]:
    """Receivers for endpoints ``0..n-1``; ``group[e]`` is e's group."""
    rng = np.random.default_rng(seed)
    n = len(group)
    eps = list(range(n))
    single = len(set(group.tolist())) == 1

    def ok(s: int, d: int) -> bool:
        return s != d and (not off_group or single or group[s] != group[d])

    for _ in range(200):
        cand = [int(x) for x in rng.permutation(eps)]
        for _sweep in range(4):
            bad = [i for i in range(n) if not ok(eps[i], cand[i])]
            if not bad:
                break
            for i in bad:
                for j in rng.integers(0, n, size=16):
                    j = int(j)
                    if ok(eps[i], cand[j]) and ok(eps[j], cand[i]):
                        cand[i], cand[j] = cand[j], cand[i]
                        break
        if all(ok(s, d) for s, d in zip(eps, cand)):
            return cand
    for shift in range(1, n):
        cand = [eps[(i + shift) % n] for i in range(n)]
        if all(ok(s, d) for s, d in zip(eps, cand)):
            return cand
    raise ValueError("no off-group derangement exists for these endpoints")


def flows(mix: dict, group: np.ndarray, config: dict) -> dict:
    """One flow per endpoint (``mix``: ``seed``, ``off_group``,
    ``size_pkts``, ``start_tick``); ``config`` is not read."""
    del config
    dst = _permutation(np.asarray(group), int(mix["seed"]),
                       bool(mix["off_group"]))
    n = len(dst)
    return {"src": np.arange(n, dtype=np.int32),
            "dst": np.asarray(dst, np.int32),
            "size": np.full(n, int(mix["size_pkts"]), np.int32),
            "start": np.full(n, int(mix["start_tick"]), np.int32)}
