#!/usr/bin/env python3
"""Smoke run of the packet engine on a TPU: the quickest proof that the
simulator's main path starts, and computes exactly, on the chip.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the lane-sharded sweep on four

One process drives every phase; nothing it starts touches JAX.

One chip:

1. kernels — the four Pallas tick kernels (tick_rank, red_ecn, flow_agg,
   spritz_select) at DF-1056 shapes against their ``kernels.ref``
   oracles, bit for bit, compiled through Mosaic (never interpret mode).
2. cells — ``repro.exp`` cells through the normal entry point
   (``runner.run``), every guard enforced:
   ``engine.dragonfly1056.permutation.quick`` (paper scale, sparse
   forms, 3 lanes), ``micro.dragonfly.adversarial.smoke`` (one-hot/GEMM
   forms, 512-packet flows) and ``serve.dragonfly.websearch.smoke``
   (segmented checkpoint/resume).  Per packet cell it prints build,
   compile and warm-run seconds.
3. checks, each bit-identical on every ``SimResult`` field:
   (a) compressed == dense ``reference=True`` stepper, one DF-1056 lane;
   (b) ``use_kernels=True`` == the jnp path on DF-1056, solo and
       batched, with ``tpu_custom_call`` in the compiled kernel program;
   (c) the micro cell with the one-hot/GEMM forms == the same cell with
       the sparse forms (``_ONEHOT_CELLS`` forced to 0).
4. cpu — the DF-1056 and micro sweeps once more on the host's CPU
   backend; reports (does not gate) whether the integer counters agree.

Four chips: DF-1056, 3 schemes x 3 seeds = 9 lanes sharded over the 4
chips (padded to 12) against the same 9 lanes unsharded on one chip,
every lane bit-identical.

Times printed here are smoke timings (host clock, ended with
``block_until_ready``), not benchmark numbers.  Any failure exits
non-zero; on success the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

PAPER_CELL = "engine.dragonfly1056.permutation.quick"
MICRO_CELL = "micro.dragonfly.adversarial.smoke"
SERVE_CELL = "serve.dragonfly.websearch.smoke"
OUT = ROOT / "results" / "chip_smoke"
RESULT_FIELDS = ("fct_ticks", "delivered", "trims", "timeouts", "ooo",
                 "retx", "done", "ticks_simulated", "steps_executed",
                 "down_violations", "rate_violations")
# the counters a CPU run is compared on (floating-point sampling may
# legitimately move FCTs between backends; see CHANGES.md)
INT_COUNTERS = ("delivered", "done", "down_violations", "rate_violations")


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def _diff(a, b, fields) -> list[str]:
    import numpy as np
    return [f for f in fields
            if not np.array_equal(np.asarray(getattr(a, f)),
                                  np.asarray(getattr(b, f)))]


def expect_same(label: str, got, want, fields=RESULT_FIELDS) -> None:
    if len(got) != len(want):
        raise SmokeFailure(f"{label}: {len(got)} lanes vs {len(want)}")
    for i, (g, w) in enumerate(zip(got, want)):
        bad = _diff(g, w, fields)
        if bad:
            raise SmokeFailure(f"{label}: lane {i} differs in {bad}")
    log(f"[check] {label}: {len(got)} lane(s) bit-identical "
        f"on {len(fields)} fields")


def cell_spec(cell_id: str):
    """The cell's base spec, built exactly as ``exp.packet`` builds it."""
    from repro.exp import matrix
    from repro.exp.workloads import build_failure, build_workload, \
        make_topology
    from repro.net.sim import build as B
    from repro.net.sim.types import SPRAY_W
    cell = matrix.CELLS[cell_id]
    topo = make_topology(cell.topology, cell.scale)
    wl = build_workload(cell, topo)
    spec_kw = dict(cell.spec_kw)
    spec_kw.update(build_failure(cell, topo).spec_kw)
    spec = B.build_spec(topo, wl.flows, SPRAY_W,
                        n_ticks=cell.n_ticks or (1 << 17), seed=0, **spec_kw)
    return cell, spec, wl.stop_flows


def timed_batch(spec, schemes, seeds, stop_flows=None):
    """One batched sweep with its phases timed apart: lane assembly,
    lower + compile, and the device run ended with block_until_ready.
    Returns (results, compiled, {phase: seconds})."""
    import jax
    from repro.net.sim import engine as E
    t0 = time.perf_counter()
    _, n_lanes, n_shard, args = E._batch_inputs(
        spec, schemes, seeds, stop_flows=stop_flows, shard=False)
    t1 = time.perf_counter()
    compiled = E._runner(spec, dense=False, batched=True,
                         shard=n_shard).lower(*args).compile()
    t2 = time.perf_counter()
    carry, t, steps = jax.block_until_ready(compiled(*args))
    t3 = time.perf_counter()
    leaves, t, steps = E._fetch_results(carry, t, steps)
    res = [E._result(E._ResultLeaves(*(x[i] for x in leaves)), t[i], steps[i])
           for i in range(n_lanes)]
    return res, compiled, {"inputs_s": t1 - t0, "compile_s": t2 - t1,
                           "run_s": t3 - t2}


def phase_kernels(report) -> None:
    """Every tick kernel on the chip vs its oracle, at DF-1056 shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    if ops._default_interpret():
        raise SmokeFailure("kernels would run in interpret mode on the chip")
    rng = np.random.default_rng(0)
    M, N, F, NP, P = 5024, 33856, 1056, 3960, 64
    port = jnp.asarray(rng.integers(-1, NP + 1, M), jnp.int32)
    red_kw = dict(qsize=88, kmin=17.6, kmax=70.4, n_ports=NP)
    red_in = (jnp.asarray(rng.integers(0, NP + 1, M), jnp.int32),
              jnp.asarray(rng.integers(0, 64, M), jnp.int32),
              jnp.asarray(rng.uniform(size=M) < 0.7),
              jnp.asarray(rng.uniform(size=M), jnp.float32),
              jnp.asarray(rng.integers(0, 300, NP), jnp.int32),
              jnp.int32(1000))
    # values above 256 (a delivered PSN) catch a bf16 product
    rows = jnp.asarray(rng.integers(0, 1 << 16, (6, N)), jnp.int32)
    pflow = jnp.asarray(rng.integers(0, F + 1, N), jnp.int32)
    sel_in = (jnp.asarray(rng.uniform(0.0, 3.0, (F, P)), jnp.float32),
              jnp.asarray(rng.uniform(size=F), jnp.float32),
              jnp.asarray(rng.integers(-1, P, F), jnp.int32),
              jnp.asarray(rng.integers(0, 60, F), jnp.int32))
    cases = {
        "tick_rank": (lambda: ops.tick_rank(port, n_ports=NP),
                      lambda: ref.tick_rank_reference(port, n_ports=NP)),
        "red_ecn": (lambda: ops.red_ecn(*red_in, **red_kw),
                    lambda: ref.red_ecn_reference(*red_in, **red_kw)),
        "flow_agg": (lambda: ops.flow_agg(rows, pflow, n_flows=F),
                     lambda: ref.flow_agg_reference(rows, pflow, n_flows=F)),
        "spritz_select": (
            lambda: ops.spritz_select(*sel_in, explore_threshold=44),
            lambda: ref.spritz_select_reference(*sel_in,
                                                explore_threshold=44)),
    }
    for name, (kern, oracle) in cases.items():
        t0 = time.perf_counter()
        got = jax.block_until_ready(kern())
        dt = time.perf_counter() - t0
        want = oracle()
        got = got if isinstance(got, (tuple, list)) else (got,)
        want = want if isinstance(want, (tuple, list)) else (want,)
        for g, w in zip(got, want):
            if not np.array_equal(np.asarray(g), np.asarray(w)):
                raise SmokeFailure(f"kernel {name} differs from its oracle")
        report["kernels"][name] = {"compile_and_run_s": dt}
        log(f"[kernels] {name}: bit-identical to oracle "
            f"(smoke timing incl. compile {dt:.3f} s)")


def phase_cells(report) -> dict:
    """The three cells through ``repro.exp``; returns base specs."""
    from repro.exp import runner
    specs = {}
    for cell_id in (PAPER_CELL, MICRO_CELL):
        t0 = time.perf_counter()
        cell, spec, stop = cell_spec(cell_id)
        build = time.perf_counter() - t0
        res, _, times = timed_batch(spec, list(cell.schemes),
                                    list(cell.seeds), stop)
        times["build_s"] = build
        specs[cell_id] = (cell, spec, stop, res)
        report["cells"][cell_id] = dict(times, n_pkt=spec.n_pkt,
                                        n_flows=spec.n_flows,
                                        n_ports=spec.n_ports,
                                        lanes=len(res),
                                        max_steps=max(r.steps_executed
                                                      for r in res))
        log(f"[cells] {cell_id}: build {build:.3f} s, "
            f"compile {times['compile_s']:.3f} s, "
            f"warm run {times['run_s']:.3f} s ({len(res)} lanes, "
            f"max {report['cells'][cell_id]['max_steps']} steps; "
            "smoke timings, not benchmark numbers)")
    if OUT.exists():
        shutil.rmtree(OUT)
    for cell_id in (PAPER_CELL, MICRO_CELL, SERVE_CELL):
        t0 = time.perf_counter()
        summary = runner.run(cells=[cell_id], force=True, out=OUT,
                             verbose=False)
        wall = time.perf_counter() - t0
        (res,) = summary.results
        entry = report["cells"].setdefault(cell_id, {})
        entry.update(cell_wall_s=wall, guards_ok=res.ok,
                     n_guards=len(res.guards))
        log(f"[cells] {cell_id}: repro.exp run {wall:.3f} s, "
            f"{len(res.guards)} guards {'pass' if res.ok else 'FAIL'}")
        if not res.ok or summary.breaches:
            raise SmokeFailure(f"{cell_id}: guard breach: {summary.breaches}")
        stored = json.loads(res.path.read_text())
        if stored["device"]["platform"] != "tpu":
            raise SmokeFailure(f"{cell_id}: result recorded on "
                               f"{stored['device']}")
    return specs


def phase_checks(report, specs) -> None:
    import numpy as np
    from repro.net.sim import build as B
    from repro.net.sim import engine as E
    cell, spec, stop, want = specs[PAPER_CELL]
    schemes = list(cell.schemes)

    # (a) event-compressed driver == dense reference stepper
    lane = B.respec_scheme(spec, "spritz_spray_w")
    comp = E.run(lane, seed=0, stop_flows=stop)
    dense = E.run(lane, seed=0, stop_flows=stop, reference=True)
    expect_same("(a) compressed == dense reference, DF-1056 spritz_spray_w",
                [comp], [dense],
                [f for f in RESULT_FIELDS if f != "steps_executed"])
    report["checks"]["a"] = {"steps": [comp.steps_executed,
                                       dense.steps_executed]}

    # (b) Pallas kernels == jnp path, solo and batched
    kspec = dataclasses.replace(spec, use_kernels=True)
    klane = dataclasses.replace(lane, use_kernels=True)
    expect_same("(b) use_kernels solo == jnp, DF-1056 spritz_spray_w",
                [E.run(klane, seed=0, stop_flows=stop)], [comp])
    got, compiled, times = timed_batch(kspec, schemes, list(cell.seeds), stop)
    n_calls = compiled.as_text().count("tpu_custom_call")
    if not n_calls:
        raise SmokeFailure("(b) kernel program holds no tpu_custom_call")
    expect_same("(b) use_kernels batched == jnp, DF-1056", got, want)
    report["checks"]["b"] = dict(times, tpu_custom_calls=n_calls)
    log(f"[check] (b) kernel program: {n_calls} tpu_custom_call ops; "
        f"compile {times['compile_s']:.3f} s, warm run "
        f"{times['run_s']:.3f} s (smoke timings)")

    # (c) one-hot/GEMM forms == sparse forms on the micro cell
    mcell, mspec, mstop, mwant = specs[MICRO_CELL]
    n_eps = int(mspec.src_ep.max()) + 1
    if mspec.n_pkt * mspec.n_flows > E._ONEHOT_CELLS or \
            E.enqueue_bound(mspec.n_pkt, mspec.n_ports, n_eps) \
            * mspec.n_ports > E._ONEHOT_CELLS:
        raise SmokeFailure("(c) micro cell no longer takes the GEMM forms")
    saved = E._ONEHOT_CELLS
    E._ONEHOT_CELLS = 0
    try:
        sparse = E.run_batch(mspec, schemes=list(mcell.schemes),
                             seeds=list(mcell.seeds), stop_flows=mstop,
                             shard=False)
    finally:
        E._ONEHOT_CELLS = saved
    expect_same("(c) GEMM forms == sparse forms, micro cell", mwant, sparse)
    report["checks"]["c"] = {
        "max_delivered_per_flow": int(max(r.delivered.max() for r in mwant)),
        "ooo_total": int(sum(r.ooo.sum() for r in mwant))}
    if not np.all([r.done.all() for r in mwant]):
        raise SmokeFailure("(c) micro cell left flows unfinished")


def phase_cpu(report, specs) -> None:
    """The same sweeps on the CPU backend of this process: reported."""
    import jax
    from repro.net.sim import engine as E
    cpu = jax.devices("cpu")[0]
    for cell_id in (MICRO_CELL, PAPER_CELL):
        cell, spec, stop, chip = specs[cell_id]
        t0 = time.perf_counter()
        with jax.default_device(cpu):
            host = E.run_batch(spec, schemes=list(cell.schemes),
                               seeds=list(cell.seeds), stop_flows=stop,
                               shard=False)
        wall = time.perf_counter() - t0
        lanes = []
        for (scheme, seed), c, h in zip(
                E.batch_lanes(list(cell.schemes), list(cell.seeds)),
                chip, host):
            lanes.append({"scheme": scheme, "seed": seed,
                          "counters_differ": _diff(c, h, INT_COUNTERS),
                          "fields_differ": _diff(c, h, RESULT_FIELDS)})
        report["cpu_vs_chip"][cell_id] = {"cpu_wall_s": wall,
                                          "lanes": lanes}
        same = sum(not lane["counters_differ"] for lane in lanes)
        exact = sum(not lane["fields_differ"] for lane in lanes)
        log(f"[cpu] {cell_id}: integer counters equal to the chip's on "
            f"{same}/{len(lanes)} lanes, every field on {exact}/"
            f"{len(lanes)} (CPU run {wall:.3f} s)")
        for lane in lanes:
            if lane["fields_differ"]:
                log(f"[cpu]   {lane['scheme']}/seed {lane['seed']}: "
                    f"differs in {lane['fields_differ']}")


def phase_four_chips(report) -> None:
    """Sharded 9-lane DF-1056 sweep vs the same lanes on one chip."""
    import jax
    from repro.net.sim import engine as E
    cell, spec, stop = cell_spec(PAPER_CELL)
    schemes, seeds = list(cell.schemes), [0, 1, 2]
    t0 = time.perf_counter()
    sharded = E.run_batch(spec, schemes=schemes, seeds=seeds,
                          stop_flows=stop, shard=True)
    t1 = time.perf_counter()
    single = E.run_batch(spec, schemes=schemes, seeds=seeds,
                         stop_flows=stop, shard=False)
    t2 = time.perf_counter()
    expect_same(f"sharded over {jax.device_count()} chips == one chip, "
                f"DF-1056 {len(schemes)} schemes x {len(seeds)} seeds",
                sharded, single)
    report["four_chips"] = {"lanes": len(sharded),
                            "sharded_s": t1 - t0, "single_s": t2 - t1,
                            "steps": [r.steps_executed for r in sharded]}
    log(f"[4 chips] sharded {t1 - t0:.3f} s, one chip {t2 - t1:.3f} s "
        "(each incl. compile; smoke timings)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        from repro import compile_cache
        import jax
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              "report a CPU run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    log(f"[device] {dev.platform} / {dev.device_kind} x {len(devs)}; "
        f"compile cache {compile_cache.enable()}")
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs)},
              "kernels": {}, "cells": {}, "checks": {}, "cpu_vs_chip": {}}
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_four_chips(report)
        else:
            phase_kernels(report)
            specs = phase_cells(report)
            phase_checks(report, specs)
            phase_cpu(report, specs)
    except Exception as e:  # any failure: no result line, non-zero exit
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    report["total_s"] = time.perf_counter() - t0
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"report_{args.chips}chip.json").write_text(
        json.dumps(report, indent=1, default=str))
    log(f"[done] all phases passed in {report['total_s']:.3f} s; report "
        f"{OUT / f'report_{args.chips}chip.json'}")
    print(json.dumps({"ok": True, "device": report["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
