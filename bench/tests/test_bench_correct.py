"""The comparison that decides ``correct``, at a CPU test size: the
program agrees with the plain reference value for value, under the
permutation and under open-loop web-search traffic, the control (the
reference with its float arithmetic in bfloat16) does not, and a run
whose timed path is broken underneath reports ``correct`` false."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = BENCH / "tests" / "data"
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import harness  # noqa: E402
from repro.net.sim import engine as E  # noqa: E402


@pytest.fixture(scope="module")
def cell():
    return harness.resolve("df72.permutation", DATA / "manifest.json",
                           DATA / "traffic")


@pytest.fixture(scope="module")
def setup(cell):
    fab = harness.reference_fabric(cell)
    fl = harness.make_flows(cell, fab)
    return fab, fl, harness.program_spec(cell, fl)


def test_program_matches_reference(cell, setup):
    fab, fl, spec = setup
    seeds = harness.lane_seeds(2**31 + 77, 0, 2)
    got = E.run_batch(spec, list(cell.mix["schemes"]), seeds)
    ref = harness.reference_lanes(cell, fab, fl, seeds)
    assert harness.mismatches(got, ref) == 0
    assert all(np.all(r.done) for r in got)


def test_control_fails_the_comparison(cell, setup):
    fab, fl, _ = setup
    seeds = harness.lane_seeds(5, 0, 2)
    ref = harness.reference_lanes(cell, fab, fl, seeds)
    ctl = harness.reference_lanes(cell, fab, fl, seeds, dtype=jnp.bfloat16)
    assert harness.mismatches(ctl, ref) > harness.LIMIT_MISMATCHES


def test_missing_lanes_count_every_value(cell, setup):
    fab, fl, _ = setup
    ref = harness.reference_lanes(cell, fab, fl, harness.lane_seeds(5, 0, 2))
    per_lane = 7 * len(fl["src"]) + 3
    assert harness.mismatches(ref[:4], ref) == 2 * per_lane


def _frozen_tick(spec, *, batched=False):
    del spec, batched
    return lambda c, t, lane=None: c


def _half_batch(run_batch):
    def half(spec, schemes, seeds, **kw):
        return run_batch(spec, schemes, seeds[: len(seeds) // 2], **kw)
    return half


def _no_exchange(run_batch):
    """Lanes past the first device's share come back as copies of it."""
    def first_share(spec, schemes, seeds, **kw):
        res = run_batch(spec, schemes, seeds, **kw)
        share = max(1, len(res) // 2)
        return [res[i % share] for i in range(len(res))]
    return first_share


def _altered_result(result):
    def altered(carry, t, steps):
        r = result(carry, t, steps)
        fct = r.fct_ticks.copy()
        fct[0] += 1
        return r._replace(fct_ticks=fct)
    return altered


FAULTS = {
    "state_unchanged": lambda mp: (mp.setattr(E, "build_tick", _frozen_tick),
                                   mp.setattr(E, "_RUNNER_CACHE", {})),
    "half_batch": lambda mp: mp.setattr(E, "run_batch",
                                        _half_batch(E.run_batch)),
    "no_exchange": lambda mp: mp.setattr(E, "run_batch",
                                         _no_exchange(E.run_batch)),
    "altered_answer": lambda mp: mp.setattr(E, "_result",
                                            _altered_result(E._result)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = harness.run(cell, seed=2**31 + 3, seconds=0.1,
                      trace=False, t0=time.perf_counter(), require_tpu=False)
    assert out["correct"] is False
    assert out["checks"]["mismatched_values"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_sound_run_is_correct(cell):
    out = harness.run(cell, seed=2**31 + 3, seconds=0.1, trace=False,
                      t0=time.perf_counter(), require_tpu=False)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["attempted"] % 6 == 0
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "sweep_s"}


@pytest.fixture(scope="module")
def websearch():
    """``df72.websearch``: open-loop flows over a packet table that binds."""
    cell = harness.resolve("df72.websearch", DATA / "manifest.json",
                           DATA / "traffic")
    fab = harness.reference_fabric(cell)
    fl = harness.make_flows(cell, fab)
    return cell, fab, fl, harness.program_spec(cell, fl)


def test_websearch_program_matches_reference(websearch):
    """Equal value for value, with staggered starts, horizon jumps on every
    lane and senders waiting for a free packet slot."""
    cell, fab, fl, spec = websearch
    seeds = harness.lane_seeds(2**31 + 78, 0, 2)
    got = E.run_batch(spec, list(cell.mix["schemes"]), seeds)
    ref = harness.reference_lanes(cell, fab, fl, seeds)
    assert harness.mismatches(got, ref) == 0
    assert all(np.all(r.done) for r in got)
    assert all(r.steps_executed < r.ticks_simulated for r in got)
    assert len(set(fl["start"].tolist())) > 1
    window = int(1.5 * cell.config["bdp_packets"]) + 4
    assert spec.n_pkt == cell.mix["pkt_slots"] < \
        np.minimum(fl["size"], window).sum() + 64
    # where the mix states no pool, the reference's results differ: the
    # stated one binds
    unbound = dataclasses.replace(
        cell, mix={k: v for k, v in cell.mix.items() if k != "pkt_slots"})
    assert harness.mismatches(
        harness.reference_lanes(unbound, fab, fl, seeds), ref) > 0


def test_websearch_control_fails_the_comparison(websearch):
    cell, fab, fl, _ = websearch
    seeds = harness.lane_seeds(6, 0, 2)
    ref = harness.reference_lanes(cell, fab, fl, seeds)
    ctl = harness.reference_lanes(cell, fab, fl, seeds, dtype=jnp.bfloat16)
    assert harness.mismatches(ctl, ref) > harness.LIMIT_MISMATCHES


def test_websearch_sound_run_is_correct(websearch):
    cell = websearch[0]
    out = harness.run(cell, seed=2**31 + 4, seconds=0.1, trace=False,
                      t0=time.perf_counter(), require_tpu=False)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] % 6 == 0


FOUR_DEVICES = """
import json, sys, time
import pytest
sys.path.insert(0, sys.argv[1])
import test_bench_correct as T
cell = T.harness.resolve("df72.permutation.x4", T.DATA / "manifest.json",
                         T.DATA / "traffic")
for fault in (None, "no_exchange"):
    with pytest.MonkeyPatch.context() as mp:
        if fault:
            T.FAULTS[fault](mp)
        out = T.harness.run(cell, seed=2**31 + 3, seconds=0.1, trace=False,
                            t0=time.perf_counter(), require_tpu=False)
    print(json.dumps({"fault": fault, "correct": out["correct"],
                      "count": out["device"]["count"], **out["checks"]}))
"""


def test_four_chip_cell_on_four_devices():
    """The lane-sharded path through the whole run, on four virtual CPU
    devices: a sound run is correct, and one whose lanes past the first
    device's share are lost is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR_DEVICES,
                        str(BENCH / "tests")], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    sound, lost = [json.loads(line) for line in p.stdout.splitlines()[-2:]]
    assert sound["correct"] is True and sound["count"] == 4, sound
    assert lost["correct"] is False and lost["mismatched_values"]["value"] > 0
