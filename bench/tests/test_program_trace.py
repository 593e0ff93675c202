"""The reduction of the program's own spans and scopes
(``program_trace.py``) and the four numbers it derives: on a hand-built
two-chip trace in which one chip finishes first, on the same trace from a
program without spans or scopes, on a recorded fragment of a chip trace,
and end to end from an ``.xplane.pb`` file."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import program_trace  # noqa: E402
import tracing  # noqa: E402
from repro.net.sim import engine as E  # noqa: E402

WHILE = "%while.1 = (s32[]) while(s32[] %x), body=%b"


def _hand(scoped=True, sweep="bench.sweep"):
    """One sweep on two chips; chip 1's loop ends at 500, chip 0's at 900."""
    spans = [(0, 1000, sweep, {})] if sweep else []
    if scoped:
        spans += [(0, 1000, "spritz.run_batch",
                   {"lanes": 6, "pad_lanes": 0, "shards": 2}),
                  (0, 100, "spritz.assemble_lanes", {}),
                  (100, 120, "spritz.dispatch", {"runner_built": 0}),
                  (120, 1000, "spritz.results",
                   {"copied_bytes": 64, "steps": 10})]

    def sc(name):
        return name if scoped else None
    ops = {0: [(150, 900, WHILE, None),
               (150, 400, "fusion.1", sc("spritz.E_enqueue")),
               (420, 880, "fusion.2", sc("spritz.policy_send")),
               (950, 980, "copy.3", None)],
           1: [(150, 500, WHILE, None),
               (150, 300, "fusion.1", sc("spritz.policy_feedback")),
               (300, 500, "fusion.4", None),
               (950, 990, "copy.3", None)]}
    return spans, ops


def _idle_of_tracing(spans, ops, n):
    """``tracing.py``'s idle seconds per chip for the same trace."""
    got = tracing.reduce_events([s[:3] for s in spans if s[2] == "bench.sweep"],
                                {c: [o[:3] for o in v] for c, v in ops.items()},
                                n_devices=n)
    return [f * got["window_s"] for f in got["idle_frac_per_chip"]]


def test_program_reduction_by_hand_on_two_chips():
    got = program_trace.reduce_program(*_hand(), n_devices=2)
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["scope_s"] == [
        {"spritz.E_enqueue": pytest.approx(250e-9),
         "spritz.policy_send": pytest.approx(460e-9),
         "outside_loop": pytest.approx(30e-9)},
        {"spritz.policy_feedback": pytest.approx(150e-9),
         "unscoped": pytest.approx(200e-9),
         "outside_loop": pytest.approx(40e-9)}]
    assert got["idle_s"] == [
        {"spritz.assemble_lanes": pytest.approx(100e-9),
         "spritz.dispatch": pytest.approx(20e-9),
         "spritz.results": pytest.approx(100e-9),
         "device_loop": pytest.approx(40e-9)},
        {"spritz.assemble_lanes": pytest.approx(100e-9),
         "spritz.dispatch": pytest.approx(20e-9),
         "spritz.results": pytest.approx(90e-9),
         "chip_wait": pytest.approx(400e-9)}]
    sweep, = got["sweeps"]
    assert sweep["assemble_lanes_s"] == pytest.approx(100e-9)
    assert sweep["results_after_loops_s"] == pytest.approx(100e-9)
    assert sweep["steps"] == 10
    assert [n for n, _, _ in sweep["spans"]] == [
        "spritz.run_batch", "spritz.assemble_lanes", "spritz.dispatch",
        "spritz.results"]
    assert sweep["spans"][0][2] == {"lanes": 6, "pad_lanes": 0, "shards": 2}
    # the causes partition the idle time that tracing.py finds
    for want, causes in zip(_idle_of_tracing(*_hand(), 2), got["idle_s"]):
        assert sum(causes.values()) == pytest.approx(want)
    assert program_trace.numbers(got) == {
        "tick_enqueue_ms": pytest.approx(1e3 * 250e-9 / 10),
        "tick_policy_ms": pytest.approx(1e3 * 460e-9 / 10),
        "lane_assembly_ms": pytest.approx(1e3 * 100e-9),
        "result_copy_ms": pytest.approx(1e3 * 100e-9)}
    text = "\n".join(program_trace.report(got))
    assert "ms per step by scope (10 steps)" in text
    assert "chip_wait" in text and "runner_built" in text


def test_program_reduction_without_the_benchmark_span():
    """An operator's trace of ``run_batch`` alone: its spans bound the
    window."""
    with_sweep = program_trace.reduce_program(*_hand(), n_devices=2)
    alone = program_trace.reduce_program(*_hand(sweep=None), n_devices=2)
    assert alone == with_sweep


def test_program_reduction_without_spans_or_scopes():
    got = program_trace.reduce_program(*_hand(scoped=False), n_devices=2)
    assert got["scope_s"][1] == {"unscoped": pytest.approx(350e-9),
                                 "outside_loop": pytest.approx(40e-9)}
    assert got["idle_s"][1] == {"bench": pytest.approx(210e-9),
                                "chip_wait": pytest.approx(400e-9)}
    assert got["sweeps"] == [{"spans": [], "assemble_lanes_s": None,
                              "results_after_loops_s": None, "steps": None}]
    assert set(program_trace.numbers(got).values()) == {None}
    assert "(unknown steps)" in program_trace.report(got)[0]


def test_scope_of_takes_the_innermost():
    assert program_trace.scope_of(
        "jit(f)/while/body/spritz.D_injection/spritz.policy_send/cond/add") \
        == "spritz.policy_send"
    assert program_trace.scope_of("jit(f)/while/body/add") is None
    assert program_trace.scope_of(None) is None


def test_program_reduction_on_a_recorded_trace():
    rec = json.loads((BENCH / "tests" / "data" / "trace_program_small.json")
                     .read_text())
    spans = [tuple(s) for s in rec["spans"]]
    ops = {int(c): [tuple(o) for o in v] for c, v in rec["ops"].items()}
    got = program_trace.reduce_program(spans, ops, n_devices=len(ops))
    want = rec["expect"]
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    for g, w in zip(got["scope_s"] + got["idle_s"],
                    want["scope_s"] + want["idle_s"]):
        assert g == pytest.approx(w, rel=1e-9)
    assert got["sweeps"] == want["sweeps"]
    assert set(got["scope_s"][0]) - {"unscoped", "outside_loop"} <= set(
        E.SCOPES)
    for w, causes in zip(_idle_of_tracing(spans, ops, len(ops)),
                         got["idle_s"]):
        assert sum(causes.values()) == pytest.approx(w, rel=1e-9)
    assert program_trace.numbers(got) == pytest.approx(want["numbers"])


def test_read_events_maps_ops_to_scopes_through_the_hlo(tmp_path, capsys):
    """A fusion whose own op_name lost the scope takes the scope most of
    the instructions it calls carry."""
    msg = program_trace._messages()
    hlo = msg["HloProto"]()
    body = hlo.hlo_module.computations.add(id=1)
    for name, op_name, calls in [
            ("while.1", "jit(f)/while", []), ("fusion.2", "", [2]),
            ("fusion.3", "jit(f)/while/body/spritz.B_service/add", [])]:
        i = body.instructions.add(name=name, called_computation_ids=calls)
        i.metadata.op_name = op_name
    fused = hlo.hlo_module.computations.add(id=2)
    for op_name in ["jit(f)/while/body/spritz.E_enqueue/scatter",
                    "jit(f)/while/body/spritz.E_enqueue/add", "scatter-add",
                    "jit(f)/while/body/spritz.B_service/max"]:
        fused.instructions.add(name="x").metadata.op_name = op_name

    space = msg["XSpace"]()
    meta = space.planes.add(name="/host:metadata")
    meta.stat_metadata[1].name = "Hlo Proto"
    meta.event_metadata[1].name = "jit_f(42)"
    meta.event_metadata[1].stats.add(metadata_id=1,
                                     bytes_value=hlo.SerializeToString())
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "program_id"
    for k, name in enumerate(["while.1", "fusion.2", "fusion.3"], 1):
        md = dev.event_metadata[k]
        md.name = f"%{name} = s32[] op()"
        md.stats.add(metadata_id=1, uint64_value=42)
    line = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    for k, off, dur in [(1, 100_000, 800_000), (2, 200_000, 300_499),
                        (3, 600_000, 250_000)]:
        line.events.add(metadata_id=k, offset_ps=off, duration_ps=dur)
    host = space.planes.add(name="/host:CPU")
    host.stat_metadata[1].name = "lanes"
    for k, name in enumerate(["bench.sweep", "spritz.run_batch", "other"], 1):
        host.event_metadata[k].name = name
    line = host.lines.add(name="python3", timestamp_ns=1000)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=1_000_000)
    line.events.add(metadata_id=2, offset_ps=0, duration_ps=1_000_000) \
        .stats.add(metadata_id=1, int64_value=3)
    line.events.add(metadata_id=3, offset_ps=0, duration_ps=5_000)
    path = tmp_path / "plugins" / "t.xplane.pb"
    path.parent.mkdir()
    path.write_bytes(space.SerializeToString())

    spans, ops, runtime = program_trace.read_events(path)
    assert spans == [(1000, 2000, "bench.sweep", {}),
                     (1000, 2000, "spritz.run_batch", {"lanes": 3})]
    assert runtime == [(1000, 2000, "spritz.run_batch"), (1000, 1005, "other")]
    assert ops == {0: [(1100, 1900, "%while.1 = s32[] op()", None),
                       (1200, 1500, "%fusion.2 = s32[] op()",
                        "spritz.E_enqueue"),
                       (1600, 1850, "%fusion.3 = s32[] op()",
                        "spritz.B_service")]}
    device, program = program_trace.reduce_both(tmp_path, n_devices=1)
    assert program == program_trace.reduce_trace(tmp_path, n_devices=1)
    assert device["window_s"] == pytest.approx(1000e-9)
    assert device["busy_s"] == pytest.approx(550e-9)  # the two fusions
    idle = device["breakdown"]["idle_gaps"]
    assert [g[0] for g in idle] == ["bench.sweep:spritz.run_batch@chip0"] * 3
    assert [g[1] for g in idle] == pytest.approx([200e-9, 150e-9, 100e-9])
    assert program_trace.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "spritz.E_enqueue" in out and "spritz.B_service" in out
