"""Benchmark orchestrator: one function per paper table/figure + LM-side
kernel microbenches.  Prints ``name,us_per_call,derived`` CSV lines.

Every suite is a thin shim over registered experiment-matrix cells
(`repro.exp`, DESIGN.md §13) — ``python -m repro.exp run`` is the
primary entry point; this CLI is kept for the legacy sweep format.

  PYTHONPATH=src python -m benchmarks.run            # quick suite (~minutes)
  PYTHONPATH=src python -m benchmarks.run --scale small   # all benches, reduced
  PYTHONPATH=src python -m benchmarks.run --scale full    # paper-scale (slow)

``--only`` takes a comma list validated against the suite table; an
unknown name exits non-zero (a typo'd CI step must not pass vacuously),
and any suite failure propagates into the exit code.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback
from pathlib import Path

SUITE_NAMES = ("memory", "engine", "motivational", "micro", "collectives",
               "incast", "trace", "failures", "fabric")


def _kernel_bench():
    """us/call for the Pallas kernels' oracles (CPU; kernels themselves are
    TPU-target and run in interpret mode — see tests)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref

    rows = []
    rng = np.random.default_rng(0)
    B, S, Hq, Hkv, D = 1, 512, 8, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    f = jax.jit(lambda q, k, v: ref.mha_reference(q, k, v))
    f(q, k, v).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        f(q, k, v).block_until_ready()
    rows.append(("kernel_mha_ref_512", (time.perf_counter() - t0) / 5 * 1e6,
                 f"B{B}xS{S}xH{Hq}"))

    F, P = 1024, 64
    w = jnp.asarray(rng.uniform(0.1, 3, (F, P)), jnp.float32)
    u = jnp.asarray(rng.uniform(size=F), jnp.float32)
    fr = jnp.asarray(rng.integers(-1, P, F), jnp.int32)
    cnt = jnp.zeros(F, jnp.int32)
    g = jax.jit(lambda *a: ref.spritz_select_reference(
        *a, explore_threshold=44))
    g(w, u, fr, cnt)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        g(w, u, fr, cnt)[0].block_until_ready()
    rows.append(("kernel_spritz_select_1024", (time.perf_counter() - t0) / 20 * 1e6,
                 f"F{F}xP{P}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="quick",
                    choices=["quick", "small", "mid", "full"])
    ap.add_argument("--only", default=None,
                    help="comma list: " + ",".join(SUITE_NAMES) + ",kernels")
    ap.add_argument("--schemes", default=None,
                    help="comma-separated registry scheme names forwarded "
                         "to every suite that takes a scheme set")
    ap.add_argument("--out", default="results/bench")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    out = Path(args.out)
    quick = args.scale == "quick"
    scale = "small" if quick else args.scale

    import inspect

    from benchmarks import (bench_collectives, bench_engine, bench_fabric,
                            bench_failures, bench_incast, bench_memory,
                            bench_micro, bench_motivational, bench_trace)
    from benchmarks.common import scheme_codes
    schemes = scheme_codes(args.schemes)

    def call(fn, **kw):
        if schemes is not None and "schemes" in inspect.signature(fn).parameters:
            kw["schemes"] = schemes
        return fn(scale, out, **kw)

    suites = {
        "memory": lambda: call(bench_memory.run),
        "engine": lambda: call(bench_engine.run),
        "motivational": lambda: call(bench_motivational.run, quick=quick),
        "micro": lambda: call(bench_micro.run, quick=quick),
        "collectives": lambda: call(bench_collectives.run, quick=quick),
        "incast": lambda: call(bench_incast.run, quick=quick),
        "trace": lambda: call(bench_trace.run, quick=quick),
        "failures": lambda: call(bench_failures.run, quick=quick),
        "fabric": lambda: call(bench_fabric.run, quick=quick),
    }
    assert set(suites) == set(SUITE_NAMES)

    only = None
    if args.only is not None:
        only = {s for s in args.only.split(",") if s}
        unknown = only - set(SUITE_NAMES) - {"kernels"}
        if unknown or not only:
            # a typo'd or empty --only must not skip every suite and
            # exit 0 — that makes a CI step pass vacuously
            sys.exit(("unknown --only suite(s): "
                      f"{sorted(unknown)}; " if unknown
                      else "empty --only selection; ")
                     + f"known: {','.join(SUITE_NAMES)},kernels")

    failed: list[str] = []
    print("name,us_per_call,derived")
    if only is None or "kernels" in only:
        try:
            for name, us, derived in _kernel_bench():
                print(f"{name},{us:.1f},{derived}")
        except Exception:
            traceback.print_exc()
            failed.append("kernels")
    for name, fn in suites.items():
        if only is not None and name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            continue
        # emit one summary CSV line per (topology x scheme) key metric
        for r in rows:
            key_metric = next((r[k] for k in
                               ("mon_fct_mean_us", "coll_duration_us",
                                "by_fct_p99_us", "fct_p99_us", "fct_mean_us",
                                "fct_us", "endpoint_table_KiB")
                               if k in r and r[k] != -1), "")
            print(f"bench_{name}_{r.get('topology','-')}_"
                  f"{r.get('scheme', r.get('workload','-'))},"
                  f"{key_metric},{r.get('trims', r.get('max_paths_per_pair',''))}",
                  flush=True)
        print(f"# {name} done in {time.time()-t0:.0f}s", flush=True)
    if failed:
        sys.exit(f"suite failure(s): {','.join(failed)}")


if __name__ == "__main__":
    main()
