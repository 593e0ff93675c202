"""The benchmark's harness on the CPU: cells resolve by name, the traffic
generator, the trace reduction, the metric arithmetic, the refusal to run
without a TPU or on another chip count than the cell's, and the pin of a
cell's lanes to its own chips."""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import random
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import harness  # noqa: E402
import program_trace  # noqa: E402
import tracing  # noqa: E402
from reference import fabric as RF  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# sha256 of the receivers (int32, little-endian) of permutation32's flows
# on each configuration
FLOW_SHA = {
    "sf1134": "f2d7b43186424f42294c29fea1dff268f25a5782c2eaf643c46f11a09e99b82f",
    "df1056": "2ed32236758780721120277f586bf58a0057d8629a33c9d5e88fd32de91dcde2",
}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = harness.resolve(name)
    assert cell.config["name"] == next(
        w["config"] for w in MANIFEST["workloads"] if w["name"] == name)
    assert cell.mix["schemes"] and cell.lanes
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "sweep_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    assert len(cell.lanes) == 3 * cell.chips * cell.mix["seeds_per_chip"]


def test_manifest_files_are_under_paths():
    paths = MANIFEST["paths"]
    for c in MANIFEST["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        assert (ROOT / c["file"]).is_file()
    for w in MANIFEST["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in MANIFEST["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in MANIFEST["end_to_end"]}


@pytest.mark.parametrize("config", sorted(FLOW_SHA))
def test_generator_is_an_off_group_permutation(config):
    cell = dataclasses.replace(
        harness.resolve(CELLS[0]),
        config=json.loads((BENCH / "configs" / f"{config}.json").read_text()),
        mix=json.loads((BENCH / "traffic" / "permutation32.json").read_text()))
    fab = harness.reference_fabric(cell)
    fl = harness.make_flows(cell, fab)
    n = fab.n_endpoints
    assert sorted(fl["dst"].tolist()) == list(range(n))
    groups = np.asarray([fab.endpoint_group(e) for e in range(n)])
    assert np.all(groups[fl["src"]] != groups[fl["dst"]])
    assert np.all(fl["size"] == cell.mix["size_pkts"])
    digest = hashlib.sha256(fl["dst"].astype("<i4").tobytes()).hexdigest()
    assert digest == FLOW_SHA[config]


def test_reference_fabrics_have_published_sizes():
    wire = RF.Wire(400.0, 4160, 25.0, 500.0, 500.0)
    df = RF.dragonfly(8, 4, 4, wire)
    assert (df.n_switches, df.n_ports, df.n_endpoints) == (264, 3960, 1056)
    sf = RF.slimfly(9, 7, wire)
    assert (sf.n_switches, sf.radix, sf.n_endpoints) == (162, 13, 1134)
    assert sf.dist.max() == 2


def test_trace_reduction_on_a_recorded_trace():
    rec = json.loads((BENCH / "tests" / "data" / "trace_small.json").read_text())
    ops = {int(c): [tuple(e) for e in v] for c, v in rec["ops"].items()}
    spans = [tuple(s) for s in rec["spans"]]
    got = tracing.reduce_events(spans, ops, n_devices=len(ops))
    want = rec["expect"]
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert got["breakdown"]["device_ops"][0][0] == want["top_op"]
    assert got["breakdown"]["idle_gaps"][0][0] == want["top_gap"]


def test_trace_reduction_leaves_out_control_flow():
    spans = [(0, 100, "bench.sweep")]
    ops = {0: [(10, 90, "%while.3 = (s32[]) while(s32[] %x), body=%b"),
               (10, 30, "%fusion.1 = s32[] fusion(s32[] %y), kind=kLoop"),
               (60, 90, "%sort.2 = s32[4] sort(s32[4] %z)")]}
    got = tracing.reduce_events(spans, ops, n_devices=1)
    assert got["busy_s"] == pytest.approx(50e-9)
    assert got["breakdown"]["device_ops"] == [
        ["sort.2 sort", pytest.approx(30e-9)],
        ["fusion.1 fusion kLoop", pytest.approx(20e-9)]]
    assert got["breakdown"]["idle_gaps"][0] == ["bench.sweep:host@chip0",
                                                pytest.approx(30e-9)]
    runtime = [(35, 55, "CommonPjRtLoadedExecutable::Execute")]
    got = tracing.reduce_events(spans, ops, 1, host_events=runtime)
    assert got["breakdown"]["idle_gaps"][0][0] == \
        "bench.sweep:CommonPjRtLoadedExecutable::Execute@chip0"


def test_trace_reduction_by_hand():
    spans = [(0, 100, "bench.sweep"), (100, 110, "bench.host"),
             (110, 200, "bench.sweep")]
    ops = {0: [(5, 50, "fusion.1"), (50, 95, "sort.2"), (115, 190, "fusion.1")],
           1: [(0, 200, "fusion.1")]}
    got = tracing.reduce_events(spans, ops, n_devices=2)
    assert got["window_s"] == pytest.approx(200e-9)
    assert got["busy_per_chip_s"] == pytest.approx([165e-9, 200e-9])
    assert got["idle_frac_per_chip"] == pytest.approx([0.175, 0.0])
    assert got["busy_s"] == pytest.approx(182.5e-9)
    assert got["breakdown"]["device_ops"] == [
        ["fusion.1", pytest.approx(320e-9)], ["sort.2", pytest.approx(45e-9)]]
    assert got["breakdown"]["idle_gaps"] == [
        ["bench.host:host@chip0", pytest.approx(20e-9)],
        ["bench.sweep:host@chip0", pytest.approx(10e-9)],
        ["bench.sweep:host@chip0", pytest.approx(5e-9)]]


def _innermost_by_scan(events, t):
    inside = [(e - s, n) for s, e, n in events if s <= t <= e]
    return min(inside)[1] if inside else None


def _reduce_events_by_scan(host_spans, device_ops, n_devices, host_events=()):
    """The reduction as it was before its gap labels took one sorted pass:
    every gap scans every span and every runtime event."""
    sweeps = [(s, e) for s, e, n in host_spans if n == "bench.sweep"]
    lo, hi = min(s for s, _ in sweeps), max(e for _, e in sweeps)
    chips = sorted(device_ops)[:n_devices]
    busy, op_time, gap_list = {}, {}, []
    for c in chips:
        ops = [(max(s, lo), min(e, hi), n)
               for s, e, n in tracing.leaves(device_ops[c]) if e > lo and s < hi]
        merged = tracing.union((s, e) for s, e, _ in ops)
        busy[c] = sum(e - s for s, e in merged) * 1e-9
        for s, e, n in ops:
            k = tracing.short_name(n)
            op_time[k] = op_time.get(k, 0.0) + (e - s) * 1e-9
        for s, e in tracing.gaps(merged, lo, hi):
            mid = (s + e) / 2
            span = _innermost_by_scan(host_spans, mid) or "outside_bench_spans"
            what = _innermost_by_scan(host_events, mid) or "host"
            gap_list.append(((e - s) * 1e-9, f"{span}:{what}@chip{c}"))
    window_s = (hi - lo) * 1e-9
    gap_list.sort(reverse=True)
    return {
        "window_s": window_s,
        "busy_s": sum(busy.values()) / len(chips),
        "busy_per_chip_s": [busy[c] for c in chips],
        "idle_frac_per_chip": [1.0 - busy[c] / window_s for c in chips],
        "breakdown": {
            "device_ops": [[n, t] for n, t in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:tracing.TOP]],
            "idle_gaps": [[label, t] for t, label in gap_list[:tracing.TOP]],
        },
    }


def _random_trace(seed):
    """Spans, ops on one to three chips and runtime events with integer
    times: nested and overlapping events, ties of duration under other
    names, and events that start or end on a gap's midpoint."""
    rng = random.Random(seed)
    spans = [(0, 400, "bench.sweep"), (400, 430, "bench.host"),
             (430, 1000, "bench.sweep"), (200, 260, "bench.inner")]
    ops = {}
    for c in range(rng.randint(1, 3)):
        ops[c] = []
        for _ in range(rng.randint(0, 40)):
            s = rng.randrange(-20, 1000)
            ops[c].append((s, s + rng.randint(1, 60), f"fusion.{rng.randrange(6)}"))
    mids = [(s + e) / 2 for c in ops for s, e in tracing.gaps(
        tracing.union((s, e) for s, e, _ in tracing.leaves(ops[c])), 0, 1000)]
    events = []
    for _ in range(rng.randint(0, 60)):
        s = rng.randrange(0, 1000)
        d = rng.choice([0, 5, 10, 10, 40, 200])
        events.append((s, s + d, rng.choice(["Execute", "Copy", "Alloc"])))
        if rng.random() < 0.3:     # nested inside it, or the same length
            events.append((s + 1, s + d - 1, "inner") if d > 2 else
                          (s, s + d, "another"))
    for m in rng.sample(mids, min(len(mids), 10)):
        d = rng.choice([4, 10])
        events.append((m, m + d, "starts_on_mid") if rng.random() < 0.5
                      else (m - d, m, "ends_on_mid"))
    return spans, ops, events


@pytest.mark.parametrize("seed", range(16))
def test_gap_labels_equal_the_per_gap_scan(seed):
    spans, ops, events = _random_trace(seed)
    for runtime in (events, ()):
        want = _reduce_events_by_scan(spans, ops, len(ops), runtime)
        assert tracing.reduce_events(spans, ops, len(ops), runtime) == want


def test_gap_labels_equal_the_per_gap_scan_on_a_recorded_trace():
    rec = json.loads((BENCH / "tests" / "data" / "trace_small.json").read_text())
    ops = {int(c): [tuple(e) for e in v] for c, v in rec["ops"].items()}
    spans = [tuple(s) for s in rec["spans"]]
    want = _reduce_events_by_scan(spans, ops, len(ops))
    assert tracing.reduce_events(spans, ops, len(ops)) == want
    # runtime events at the recorded gaps' midpoints, ties among them
    merged = tracing.union((s, e) for s, e, _ in tracing.leaves(ops[0]))
    mids = [(s + e) / 2 for s, e in tracing.gaps(merged, *_window(spans))]
    runtime = [(m - 1000, m + 1000, "b") for m in mids[::3]] + \
        [(m - 1000, m + 1000, "a") for m in mids[::6]] + \
        [(m, m + 5, "on") for m in mids[1::5]]
    assert tracing.reduce_events(spans, ops, len(ops), runtime) == \
        _reduce_events_by_scan(spans, ops, len(ops), runtime)


def _window(spans):
    sweeps = [(s, e) for s, e, n in spans if n == "bench.sweep"]
    return min(s for s, _ in sweeps), max(e for _, e in sweeps)


def test_innermost_takes_the_shortest_then_the_smaller_name():
    events = [(0, 10, "outer"), (2, 6, "b"), (4, 8, "a"), (6, 6, "point")]
    assert tracing.innermost(events, [11, 1, 6, 5, 10, 3, -1]) == [
        None, "outer", "point", "a", "outer", "b", None]
    assert tracing.innermost([], [1.5]) == [None]


def _run(sweeps, trace=None):
    cell = harness.resolve(CELLS[0])
    return harness.Run(cell=cell, setup_s=1.5, build_s=0.5, compile_s=0.25,
                       sweeps=sweeps, trace=trace,
                       traced=len(sweeps) if trace else 0)


def _sweep(steps, seconds):
    return harness.Sweep(seeds=[0] * len(steps), seconds=seconds,
                         results=[types.SimpleNamespace(steps_executed=s)
                                  for s in steps])


def test_metric_arithmetic_on_fixed_inputs():
    run = _run([_sweep([300, 200, 100], 4.0), _sweep([400, 400, 200], 5.0)],
               trace={"busy_per_chip_s": [6.3, 3.5],
                      "idle_frac_per_chip": [0.1, 0.5]})
    assert run.traced == 2
    e2e = harness.metrics(run, trace=False)
    assert e2e["sweep_s"] == {"value": 4.5, "unit": "s"}
    assert e2e["setup_s"]["value"] == 1.5
    read = harness.metric_reader
    # 1 - (600 + 1000) / (3 * 300 + 3 * 400)
    assert read("lane_waste_frac")(run) == pytest.approx(1 - 1600 / 2100)
    assert read("step_ms")(run) == pytest.approx(1e3 * 6.3 / 700)
    assert read("device_idle_frac")(run) == pytest.approx(0.3)
    assert read("build_s")(run) == 0.5 and read("compile_s")(run) == 0.25
    assert read("step_ms")(_run(run.sweeps)) is None


def test_lane_seeds_differ_per_sweep_and_take_large_seeds():
    a = harness.lane_seeds(2**31 + 5, 0, 4)
    b = harness.lane_seeds(2**31 + 5, 1, 4)
    assert a != b and len(set(a)) == 4
    assert all(0 <= s < 2**31 for s in a + b)
    assert harness.lane_seeds(2**31 + 5, 0, 4) == a


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1"],
                       env=env, cwd=ROOT, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


PROGRAM = {"window_s": 1.0, "idle_s": [{}, {}],
           "scope_s": [{"spritz.E_enqueue": 0.5, "spritz.policy_send": 0.2,
                        "spritz.policy_feedback": 0.1},
                       {"spritz.E_enqueue": 0.7, "spritz.policy_send": 0.05}],
           "sweeps": [{"spans": [], "assemble_lanes_s": 0.2,
                       "results_after_loops_s": 0.3, "steps": 120},
                      {"spans": [], "assemble_lanes_s": 0.4,
                       "results_after_loops_s": 0.5, "steps": 80}]}


@pytest.mark.parametrize("name,want", [
    ("tick_enqueue_ms", 1e3 * 0.7 / 200),        # the busiest chip
    ("tick_policy_ms", 1e3 * 0.3 / 200),
    ("lane_assembly_ms", 1e3 * 0.3),             # the mean over sweeps
    ("result_copy_ms", 1e3 * 0.4)])
def test_program_metric_readers(name, want):
    read = harness.metric_reader(name)
    run = _run([_sweep([120, 100, 90], 4.0), _sweep([80, 80, 70], 5.0)])
    assert read(run) is None
    run.program = PROGRAM
    assert read(run) == pytest.approx(want)
    assert program_trace.numbers(PROGRAM)[name] == read(run)


def test_chip_wait_frac_reader():
    read = harness.metric_reader("chip_wait_frac")
    run = _run([_sweep([120, 100, 90], 4.0)])
    assert read(run) is None
    run.program = dict(PROGRAM, window_s=2.0, idle_s=[
        {"spritz.results": 0.4}, {"chip_wait": 0.3, "bench": 0.1},
        {"chip_wait": 0.5}, {"chip_wait": 0.0}])
    assert read(run) == pytest.approx((0.3 + 0.5) / 4 / 2.0)
    run.program = dict(PROGRAM, idle_s=[{"spritz.results": 0.4}])
    assert read(run) is None                      # one chip waits for none


def _fake_devices(n):
    return [types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                                  memory_stats=lambda: {"peak_bytes_in_use": 7})
            for _ in range(n)]


@pytest.fixture
def stub_program(monkeypatch):
    """A run with no fabric, program or reference behind it: ``run_batch``
    records its ``shard`` and returns lanes that equal the reference's."""
    import jax
    from reference.sim import FIELDS
    from repro.net.sim import engine as E
    calls = []

    def lanes(seeds, n):
        return [types.SimpleNamespace(steps_executed=3, **{
            f: np.zeros(2, np.int32) + (k + 1) for f in FIELDS})
            for k in range(n)]

    def run_batch(spec, schemes, seeds, shard=None, until_tick=None):
        calls.append(shard)
        return lanes(seeds, len(schemes) * len(seeds))

    monkeypatch.setattr(E, "run_batch", run_batch)
    for name in ("reference_fabric", "program_spec"):
        monkeypatch.setattr(harness, name, lambda *a: None)
    monkeypatch.setattr(harness, "make_flows", lambda *a: {})
    monkeypatch.setattr(harness, "reference_lanes",
                        lambda cell, fab, fl, seeds: lanes(seeds, len(cell.lanes)))

    def use(n_devices):
        monkeypatch.setattr(jax, "devices", lambda: _fake_devices(n_devices))
        return calls
    return use


@pytest.mark.parametrize("name,n_devices,shard", [
    ("sf1134.permutation", 1, False), ("sf1134.permutation", 4, False),
    ("df1056.permutation.x4", 4, True)])
def test_cell_runs_on_its_own_chip_count(stub_program, name, n_devices, shard):
    calls = stub_program(n_devices)
    cell = harness.resolve(name)
    out = harness.run(cell, seed=2**31 + 9, seconds=0.01, trace=False,
                      t0=0.0)
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == cell.chips
    assert len(calls) >= 2 and set(calls) == {shard}   # warm-up and window


@pytest.mark.parametrize("n_devices", [1, 2, 8])
def test_four_chip_cell_refuses_another_chip_count(stub_program, n_devices):
    calls = stub_program(n_devices)
    with pytest.raises(harness.BenchError, match="needs 4 chips"):
        harness.run(harness.resolve("df1056.permutation.x4"), seed=1,
                    seconds=0.01, trace=False, t0=0.0)
    assert calls == []
