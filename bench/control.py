#!/usr/bin/env python3
"""Readings that set the limit of the benchmark's comparison, on the chip.

    python3 bench/control.py --workload sf1134.permutation \\
        --seeds 11,12,13 --control-seeds 11,12,13

In one process, for each ``--seeds`` value, one whole sweep of the
program (the lanes of a run's sweep 0 with that ``--seed``) is compared
with the plain float32 reference: the lower reading.  For each
``--control-seeds`` value the control, the same reference with its
float arithmetic (window, path sampler, RED) in bfloat16 (the precision below the
configuration's float32), is put in the program's place and compared the
same way: the upper reading.  Prints one JSON line per seed and a
summary; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def readings(cell, seeds, control_seeds, *, log=print) -> dict:
    import jax.numpy as jnp
    import harness
    from repro.net.sim import engine as E
    fab = harness.reference_fabric(cell)
    fl = harness.make_flows(cell, fab)
    spec = harness.program_spec(cell, fl)
    n = cell.mix["seeds_per_chip"] * cell.chips
    out = {"program": {}, "control": {}}
    for s in sorted(set(seeds) | set(control_seeds)):
        lane_seeds = harness.lane_seeds(s, 0, n)
        ref = harness.reference_lanes(cell, fab, fl, lane_seeds)
        if s in seeds:
            t = time.perf_counter()
            got = E.run_batch(spec, list(cell.mix["schemes"]), lane_seeds,
                              shard=cell.chips > 1)
            out["program"][s] = harness.mismatches(got, ref)
            log(json.dumps({"seed": s, "program_mismatches": out["program"][s],
                            "sweep_s": time.perf_counter() - t,
                            "max_steps": max(r.steps_executed for r in got)}))
        if s in control_seeds:
            ctl = harness.reference_lanes(cell, fab, fl, lane_seeds,
                                          dtype=jnp.bfloat16)
            out["control"][s] = harness.mismatches(ctl, ref)
            log(json.dumps({"seed": s,
                            "control_mismatches": out["control"][s]}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import jax
    import harness
    from repro import compile_cache
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    compile_cache.enable()
    parse = lambda v: [int(x) for x in v.split(",") if x]  # noqa: E731
    out = readings(harness.resolve(args.workload), parse(args.seeds),
                   parse(args.control_seeds))
    print(json.dumps({"workload": args.workload,
                      "lower": max(out["program"].values(), default=None),
                      "upper": min(out["control"].values(), default=None),
                      **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
