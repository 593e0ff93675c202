"""One benchmark run of one cell: set-up, the measured window of whole
sweeps through ``engine.run_batch``, the optional profiler trace, the
comparison with the plain reference, and the result line.

Everything that belongs to a cell is found by name from
``BENCHMARK.json``: the configuration file, ``traffic/<mix>.json`` (whose
``pattern`` names ``traffic/<pattern>.py``) and ``metrics/<metric>.py``
for each per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import shutil
import sys
import time
import types
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
# events JAX's monitoring records whenever a program is traced, lowered,
# compiled or loaded from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
LIMIT_MISMATCHES = 0   # the simulation is exact: every value must agree
PKT_SLOTS = 1 << 16    # packet-table cap where the mix states no ``pkt_slots``
TRACED_SWEEPS = 2      # a --trace 1 run traces the window's first sweeps


class BenchError(Exception):
    """A run that cannot produce a result (exit non-zero, no result line)."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list       # metric entries of BENCHMARK.json
    per_layer: list

    @property
    def lanes(self) -> list[tuple[str, int]]:
        """(scheme, seed slot) in ``run_batch``'s scheme-major order."""
        n = self.mix["seeds_per_chip"] * self.chips
        return [(s, k) for s in self.mix["schemes"] for k in range(n)]


def _for_cell(entries: list, name: str) -> list:
    return [m for m in entries if name in m.get("workloads", [name])]


def resolve(name: str, manifest: Path = ROOT / "BENCHMARK.json",
            traffic: Path = BENCH / "traffic") -> Cell:
    """The cell ``name`` with its configuration (``file`` is relative to
    the manifest), its traffic mix (``<traffic>/<mix>.json``) and its
    metrics."""
    bm = json.loads(manifest.read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in {manifest.name}; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bm["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((manifest.parent / cfg["file"]).read_text()),
        mix=json.loads((traffic / f"{w['traffic']}.json").read_text()),
        end_to_end=_for_cell(bm["end_to_end"], name),
        per_layer=_for_cell(bm["per_layer"], name))


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(run) -> float | None``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def lane_seeds(seed: int, sweep: int, n: int) -> list[int]:
    """Lane seeds of sweep ``sweep`` of a run with ``--seed seed``: 31-bit
    values drawn from (seed, sweep), so every sweep differs."""
    ss = np.random.SeedSequence([seed % (1 << 64), sweep])
    return [int(x) & 0x7FFFFFFF for x in ss.generate_state(n)]


class CompileLog:
    """Counts JAX's compile-side events (and sums their seconds)."""

    def __init__(self):
        self.events: list[tuple[str, float]] = []

    def __call__(self, event: str, duration: float, **_):
        if event in COMPILE_EVENTS:
            self.events.append((event, duration))

    def seconds(self) -> float:
        return float(sum(d for _, d in self.events))


@dataclasses.dataclass
class Sweep:
    seeds: list
    results: list           # the program's SimResult per lane
    seconds: float


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    cell: Cell
    setup_s: float
    build_s: float
    compile_s: float
    sweeps: list
    trace: dict | None = None   # tracing.reduce_events of the traced sweeps
    traced: int = 0             # the window's first ``traced`` sweeps
    program: dict | None = None  # program_trace.reduce_program of the same trace


# ---------------------------------------------------------------- set-up
def reference_fabric(cell: Cell):
    from reference import fabric as RF
    cfg = cell.config
    return RF.build_fabric(cfg["topology"], RF.Wire(**cfg["wire"]))


def make_flows(cell: Cell, fab) -> dict:
    from traffic import generator
    group = np.asarray([fab.endpoint_group(e) for e in range(fab.n_endpoints)])
    return generator.flows(cell.mix, group, cell.config)


def pkt_slots(cell: Cell) -> int:
    """The packet-table cap both the program and the reference size by:
    while it binds, senders wait for a free slot, so it is part of the
    result."""
    return int(cell.mix.get("pkt_slots", PKT_SLOTS))


def program_spec(cell: Cell, fl: dict):
    """The program's topology and spec, built through its normal path."""
    from repro.net.sim import build as B
    mod, fn = cell.config["program_topology"].split(":")
    kw = {k: v for k, v in cell.config["topology"].items() if k != "kind"}
    topo = getattr(importlib.import_module(mod), fn)(**kw)
    flows = [B.Flow(int(s), int(d), int(n), int(t))
             for s, d, n, t in zip(fl["src"], fl["dst"], fl["size"], fl["start"])]
    return B.build_spec(topo, flows, "spritz_spray_w",
                        n_ticks=int(cell.mix["n_ticks"]),
                        seed=int(cell.config["path_seed"]),
                        max_paths=int(cell.config["max_paths"]),
                        w_scale=float(cell.config["w_scale"]),
                        n_pkt_cap=pkt_slots(cell))


# ------------------------------------------------------------- correctness
def reference_lanes(cell: Cell, fab, fl: dict, seeds: list, dtype=None):
    """The plain reference's results for one sweep's lanes (float32, or
    ``dtype`` for the control), as objects with ``FIELDS``."""
    import jax.numpy as jnp
    from reference import fabric as RF
    from reference import sim as RS
    cfg = cell.config
    tables = RF.flow_tables(fab, list(zip(fl["src"].tolist(), fl["dst"].tolist())),
                            max_paths=int(cfg["max_paths"]),
                            w_scale=float(cfg["w_scale"]),
                            path_seed=int(cfg["path_seed"]))
    sz = RS.sizes(tables, fl, bdp=int(cfg["bdp_packets"]),
                  n_ticks=int(cell.mix["n_ticks"]), n_ports=fab.n_ports,
                  pkt_slots=pkt_slots(cell))
    lanes = [(s, seeds[k]) for s, k in cell.lanes]
    return [types.SimpleNamespace(**r) for r in RS.simulate(
        tables, fl, sz, fab.n_ports, lanes, dtype=dtype or jnp.float32)]


def mismatches(results: list, ref: list) -> int:
    """Values that differ between two lists of lane results; a missing
    lane counts every value it should have had."""
    from reference.sim import FIELDS
    per_lane = sum(np.asarray(getattr(ref[0], f)).size for f in FIELDS)
    bad = per_lane * abs(len(ref) - len(results))
    for got, want in zip(results, ref):
        for f in FIELDS:
            a, b = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
            bad += int(b.size if a.shape != b.shape else np.sum(a != b))
    return bad


# ------------------------------------------------------------------- run
def run(cell: Cell, *, seed: int, seconds: float, trace: bool, t0: float,
        require_tpu: bool = True) -> dict:
    """One run of ``cell``; ``t0`` is the process's start on the host clock.
    Returns the result line's object."""
    import jax
    from jax import monitoring
    devs = jax.devices()
    platform = devs[0].platform
    if require_tpu and platform != "tpu":
        raise BenchError(f"no TPU: JAX found {platform}; a benchmark never "
                         "runs on the CPU")
    # the program shards a batch over every visible device, so a cell on
    # several chips runs only where it sees exactly its own
    if len(devs) < cell.chips or (cell.chips > 1 and len(devs) != cell.chips):
        raise BenchError(f"{cell.name} needs {cell.chips} chips, JAX sees "
                         f"{len(devs)}")
    log(f"device {platform} / {devs[0].device_kind} x {len(devs)}")
    compiles = CompileLog()
    monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _run(cell, seed, seconds, trace, t0, devs, compiles)
    finally:
        monitoring.unregister_event_duration_listener(compiles)


def _run(cell, seed, seconds, trace, t0, devs, compiles) -> dict:
    import jax
    from repro.net.sim import engine as E
    platform = devs[0].platform

    tb = time.perf_counter()
    fab = reference_fabric(cell)
    fl = make_flows(cell, fab)
    spec = program_spec(cell, fl)
    build_s = time.perf_counter() - tb
    schemes = list(cell.mix["schemes"])
    n_seeds = cell.mix["seeds_per_chip"] * cell.chips
    shard = cell.chips > 1
    # warm the window's one program (and the per-lane host ops) on a few
    # ticks: ``until_tick`` is a traced bound, so this is the same program
    E.run_batch(spec, schemes, lane_seeds(seed, 0, n_seeds), shard=shard,
                until_tick=int(cell.mix["warmup_ticks"]))
    n_setup_compiles = len(compiles.events)
    compile_s = compiles.seconds()

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    sweeps: list[Sweep] = []
    tw = time.perf_counter()
    setup_s = tw - t0
    while time.perf_counter() - tw < seconds:
        seeds = lane_seeds(seed, len(sweeps), n_seeds)
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.sweep"):
            res = E.run_batch(spec, schemes, seeds, shard=shard)
        sweeps.append(Sweep(seeds, res, time.perf_counter() - ts))
        if trace and len(sweeps) == TRACED_SWEEPS:
            jax.profiler.stop_trace()
    if trace and len(sweeps) < TRACED_SWEEPS:
        jax.profiler.stop_trace()
    in_window = len(compiles.events) - n_setup_compiles
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell.chips])
    run_ = Run(cell=cell, setup_s=setup_s, build_s=build_s,
               compile_s=compile_s, sweeps=sweeps,
               traced=min(len(sweeps), TRACED_SWEEPS) if trace else 0)
    if trace:
        import program_trace
        run_.trace, run_.program = program_trace.reduce_both(
            TRACE_DIR, n_devices=cell.chips)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    pick = int(np.random.default_rng([seed % (1 << 64), 7]).integers(len(sweeps)))
    tc = time.perf_counter()
    bad = mismatches(sweeps[pick].results,
                     reference_lanes(cell, fab, fl, sweeps[pick].seeds))
    log(f"reference compared sweep {pick} of {len(sweeps)} in "
        f"{time.perf_counter() - tc:.3f} s")
    checks = {"mismatched_values": {"value": bad, "limit": LIMIT_MISMATCHES},
              "window_compiles": {"value": in_window, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    lanes_run = [r for s in sweeps for r in s.results]
    out = {"correct": correct,
           "attempted": len(lanes_run),
           "failed": sum(not bool(np.all(r.done)) for r in lanes_run),
           "metrics": metrics(run_, trace),
           "device": {"platform": platform, "kind": devs[0].device_kind,
                      "count": cell.chips, "memory_peak_bytes": peak}}
    if trace:
        out["device"].update(busy_s=run_.trace["busy_s"],
                             window_s=run_.trace["window_s"])
        out["breakdown"] = run_.trace["breakdown"]
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return out


def metrics(run_: Run, trace: bool) -> dict:
    if not trace:
        vals = {"setup_s": run_.setup_s,
                "sweep_s": sum(s.seconds for s in run_.sweeps) / len(run_.sweeps)}
        entries = run_.cell.end_to_end
    else:
        entries = run_.cell.per_layer
        vals = {m["name"]: metric_reader(m["name"])(run_) for m in entries}
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in entries if vals.get(m["name"]) is not None}


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
