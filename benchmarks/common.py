"""Shared benchmark dispatch + helpers.

The cell-running machinery lives in the experiment-matrix subsystem
(`repro.exp`, DESIGN.md §13); every ``bench_*`` module is a thin shim
over registered matrix cells.  This module keeps the shared CLI
(``bench_cli``), the scheme-set tables, CSV/JSON writers, and
re-exports the packet-cell statistics helpers (``run_schemes``,
``fct_stats``, ``completed_after``) for callers of the legacy API.
"""
from __future__ import annotations

import argparse
import csv
import inspect
import json
from pathlib import Path

from repro.exp.packet import (completed_after, fct_stats,  # noqa: F401
                              run_schemes)
from repro.exp.workloads import make_topology
from repro.net.policies import registry as REG

# scheme sets come from the sender-policy registry (DESIGN.md §11): every
# registered scheme benchmarks by default; ``failover`` flags the schemes
# able to adapt around failures (bench_failures' set — Minimal, ECMP,
# UGAL-L and Flicr cannot finish within the paper's time limit there).
ALL_SCHEMES = [p.code for p in REG.all_policies()]
ADAPTIVE_SCHEMES = [p.code for p in REG.failover_policies()]


def scheme_codes(arg) -> list[int] | None:
    """Shared ``--schemes`` filter: a comma-separated string (or iterable)
    of registry names — integer codes accepted as a deprecation shim."""
    if arg is None:
        return None
    if isinstance(arg, str):
        arg = [s for s in arg.split(",") if s]
    return [REG.as_code(int(s) if isinstance(s, str) and s.isdigit() else s)
            for s in arg]


def scheme_names(arg) -> list[str] | None:
    """Same filter, resolved to registry names (what `repro.exp` takes)."""
    codes = scheme_codes(arg)
    if codes is None:
        return None
    return [REG.resolve(c).name for c in codes]


def bench_cli(run, argv=None, **fixed):
    """Shared CLI for every ``bench_*`` module: ``--full/--scale``,
    ``--quick``, ``--out`` and the registry-name ``--schemes`` filter
    (e.g. ``--schemes spritz_scout,reps``).  Keyword arguments the
    bench's ``run`` does not accept are dropped."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale topologies (slow)")
    ap.add_argument("--scale", default=None,
                    choices=["small", "mid", "full"])
    ap.add_argument("--quick", action="store_true",
                    help="single fast cell (CI smoke)")
    ap.add_argument("--schemes", default=None,
                    help="comma-separated registry scheme names "
                         f"(known: {','.join(REG.names())})")
    ap.add_argument("--out", default="results/bench")
    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()
    scale = args.scale or ("full" if args.full else "small")
    kw = dict(schemes=scheme_codes(args.schemes), quick=args.quick, **fixed)
    accepted = inspect.signature(run).parameters
    for flag in ("schemes", "quick"):
        if kw.get(flag) and flag not in accepted:
            ap.error(f"--{flag} is not supported by this benchmark")
    kw = {k: v for k, v in kw.items() if k in accepted}
    return run(scale, Path(args.out), **kw)


def run_bench_cells(bench: str, scale: str, schemes=None, quick=False,
                    check=False, cells=None) -> list[dict]:
    """The bench-shim dispatcher: select the bench's registered matrix
    cells for the requested scale (``quick`` → the smoke-tier cells),
    run them through `repro.exp.runner`, and return flat legacy-style
    rows.  ``check=True`` turns any guard breach into ``SystemExit``."""
    from repro.exp import matrix, runner
    if cells is None:
        if quick:
            sel = matrix.cells(tier="smoke", bench=bench) \
                or matrix.cells(tier="ci", bench=bench)
            scale_override = None
        elif scale == "full":
            sel = matrix.cells(tier="full", bench=bench)
            scale_override = None
        else:
            sel = matrix.cells(tier="ci", bench=bench)
            scale_override = scale if scale != "small" else None
        cells = [c.cell_id for c in sel]
    else:
        scale_override = None
    summary = runner.run(cells=cells, schemes=scheme_names(schemes),
                         scale=scale_override, results_md=None,
                         check=check)
    return summary.rows


def topologies(scale: str):
    """Legacy helper: the matrix's packet topology pair at one scale."""
    return {name: make_topology(name, scale)
            for name in ("dragonfly", "slimfly")}


def write_csv(path: Path, rows: list[dict]):
    path.parent.mkdir(parents=True, exist_ok=True)
    if not rows:
        return
    keys = sorted({k for r in rows for k in r})
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=keys)
        w.writeheader()
        w.writerows(rows)


def write_json(path: Path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))
