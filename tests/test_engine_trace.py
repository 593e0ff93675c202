"""The engine's trace points: the device scopes of the tick phases reach
every compiled runner's ``op_name`` metadata, and ``run_batch``'s host
spans land in a profiler trace, nested, with their counts."""
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.net.sim import build as B
from repro.net.sim import engine as E
from repro.net.sim.failures import FailureSchedule, sample_links
from repro.net.sim.types import SPRAY_W
from repro.net.topology.dragonfly import make_dragonfly
from repro.net.workloads import synthetic

DF = make_dragonfly(4, 2, 2)   # 72 endpoints: the benchmark's test size
SCHEMES = ["ecmp", "ugal_l", "spritz_spray_w"]


def _spec(n_ticks=1 << 11):
    """A permutation with a mid-run link failure, so phase A0 is traced."""
    plan = FailureSchedule(DF).fail_links(60, sample_links(DF, 4, seed=3)) \
        .recover(400)
    return B.build_spec(DF, synthetic.permutation(DF, 32, seed=1), SPRAY_W,
                        n_ticks=n_ticks, failure_plan=plan)


@pytest.mark.parametrize("batched", [False, True], ids=["solo", "batched"])
def test_phase_scopes_reach_the_compiled_program(batched):
    spec = _spec()
    if batched:
        args = E._batch_inputs(spec, SCHEMES, [0])[3]
    else:
        args = (E.init_carry(spec, 0), jnp.ones(spec.n_flows, bool),
                jnp.int32(-1), jnp.int32(0), jnp.int32(spec.n_ticks))
    hlo = E._runner(spec, dense=False, batched=batched).lower(*args) \
        .compile().as_text()
    scopes = {part for name in re.findall(r'op_name="([^"]*)"', hlo)
              for part in name.split("/") if part.startswith("spritz.")}
    assert scopes == set(E.SCOPES)


def _host_events(trace_dir: Path, prefix: str
                 ) -> list[tuple[int, int, str, dict]]:
    """(start, end, name, stats) of every host event named ``prefix*``."""
    path, = Path(trace_dir).rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    return sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                    dict(ev.stats))
                   for plane in data.planes if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith(prefix)), key=lambda ev: ev[:3])


def _spans(trace_dir: Path) -> list[tuple[int, int, str, dict]]:
    """(start, end, name, stats) of every ``spritz.*`` host span."""
    return _host_events(trace_dir, "spritz.")


def test_run_batch_spans_in_a_profiler_trace(tmp_path):
    spec = _spec(n_ticks=(1 << 11) + 1)   # a spec no other test compiled
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            res = E.run_batch(spec, SCHEMES, [0, 1], until_tick=64)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    calls = [s for s in spans if s[2] == "spritz.run_batch"]
    assert len(calls) == 2
    for (lo, hi, _, stats), built in zip(calls, (1, 0)):
        assert stats == {"lanes": 6, "pad_lanes": 0, "shards": 0}
        inner = {n: st for s, e, n, st in spans
                 if lo <= s and e <= hi and n != "spritz.run_batch"}
        assert set(inner) == set(E.SPANS) - {"spritz.run_batch"}
        assert inner["spritz.dispatch"] == {"runner_built": built}
        # 7 per-flow arrays per lane: six int32 counters and ``done``;
        # 10 arrays fetched: the 8 result leaves, the tick and the steps
        assert inner["spritz.results"] == {
            "copied_bytes": 6 * spec.n_flows * (6 * 4 + 1),
            "transfers": 10,
            "steps": max(r.steps_executed for r in res)}


@pytest.mark.parametrize("n_seeds, return_carry", [(1, False), (6, False),
                                                  (1, True), (6, True)],
                         ids=["1lane", "6lanes", "1lane-carry",
                              "6lanes-carry"])
def test_results_span_counts_transfers_not_lanes(tmp_path, n_seeds,
                                                 return_carry):
    """The results are fetched in a fixed number of transfers, whatever
    the lane count, and no jitted op runs while they are copied out."""
    spec = _spec(n_ticks=(1 << 11) + 1)
    seeds = list(range(n_seeds))
    E.run_batch(spec, SCHEMES[:1], seeds, until_tick=64)   # warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = E.run_batch(spec, SCHEMES[:1], seeds, until_tick=64,
                          return_carry=return_carry)
    finally:
        jax.profiler.stop_trace()
    res = out[0] if return_carry else out
    assert len(res) == n_seeds
    carry = E.init_carry(spec, 0)
    n_leaves = len(jax.tree.leaves(carry))
    copied = n_seeds * spec.n_flows * (6 * 4 + 1)
    if return_carry:
        copied += n_seeds * E.live_carry_bytes(carry)
    (lo, hi, _, stats), = [sp for sp in _spans(tmp_path)
                           if sp[2] == "spritz.results"]
    assert stats == {"copied_bytes": copied,
                     "transfers": 10 + n_leaves * return_carry,
                     "steps": max(r.steps_executed for r in res)}
    jitted = [n for s, e, n, _ in _host_events(tmp_path, "PjitFunction")
              if lo <= s and e <= hi]
    assert jitted == []
