"""How results reach the host: ``run`` and ``run_batch`` fetch the arrays
a result reads in one ``jax.device_get`` and slice lanes on the host.

Per lane, every field keeps its dtype and shape, equals what a solo
``run`` of the same scheme and seed returns, and owns its memory.  On
four devices the lane-sharded sweep (6 lanes padded to 8) returns the
same results and carry states as the single-device vmap, with the pad
lanes dropped: that case runs in a child process with four virtual CPU
devices, since the device count is fixed when jax initializes.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.net.sim import build as B
from repro.net.sim import engine as E
from repro.net.sim.types import ECMP, SPRAY_W, UGAL_L
from repro.net.topology.dragonfly import make_dragonfly

ROOT = Path(__file__).resolve().parents[1]
DF = make_dragonfly(4, 2, 2)
FLOWS = [B.Flow(e, 40 + (e % 3), 40 + 8 * (e % 2), start_tick=16 * e)
         for e in range(6)]
SCHEMES = [ECMP, UGAL_L, SPRAY_W]
SEEDS = [0, 1]
ARRAYS = ("fct_ticks", "delivered", "trims", "timeouts", "ooo", "retx",
          "done")
COUNTERS = ("ticks_simulated", "steps_executed", "down_violations",
            "rate_violations")


def _spec():
    return B.build_spec(DF, FLOWS, SPRAY_W, n_ticks=1 << 12)


def _state_leaves(state: dict) -> dict:
    """A nested carry state's arrays by their path."""
    out = {}
    for k, v in state.items():
        if isinstance(v, dict):
            out.update({f"{k}/{p}": a for p, a in _state_leaves(v).items()})
        else:
            out[k] = v
    return out


def assert_same_result(a, b, ctx):
    for name in ARRAYS:
        x, y = getattr(a, name), getattr(b, name)
        assert isinstance(x, np.ndarray), (ctx, name)
        assert (x.dtype, x.shape) == (y.dtype, y.shape), (ctx, name)
        assert np.array_equal(x, y), (ctx, name)
    for name in COUNTERS:
        assert type(getattr(a, name)) is int, (ctx, name)
        assert getattr(a, name) == getattr(b, name), (ctx, name)


def assert_same_state(a, b, ctx):
    la, lb = _state_leaves(a), _state_leaves(b)
    assert la.keys() == lb.keys(), ctx
    for k in la:
        assert (la[k].dtype, la[k].shape) == (lb[k].dtype, lb[k].shape), \
            (ctx, k)
        assert np.array_equal(la[k], lb[k]), (ctx, k)


def assert_lanes_own_memory(results, states):
    """Each lane's arrays are its own: no view into the lane-stacked
    buffer, and no memory in common with another lane's."""
    arrays = [[getattr(r, n) for n in ARRAYS] for r in results]
    arrays = [a + list(_state_leaves(s).values())
              for a, s in zip(arrays, states)]
    assert all(x.flags.owndata for a in arrays for x in a)
    for i in range(len(arrays)):
        for j in range(i):
            for x in arrays[i]:
                assert not any(np.shares_memory(x, y) for y in arrays[j]), \
                    (i, j)


def check_sharded_lanes():
    """Run in the four-device child: sharded == vmapped, lane by lane."""
    assert jax.device_count() == 4
    spec = _spec()
    n = len(SCHEMES) * len(SEEDS)
    got, got_st = E.run_batch(spec, SCHEMES, SEEDS, shard=True,
                              return_carry=True)
    want, want_st = E.run_batch(spec, SCHEMES, SEEDS, shard=False,
                                return_carry=True)
    plain = E.run_batch(spec, SCHEMES, SEEDS, shard=True)
    assert len(got) == len(got_st) == len(want) == len(plain) == n
    for lane, (g, w, p, gs, ws) in enumerate(zip(got, want, plain, got_st,
                                                 want_st)):
        assert_same_result(g, w, ("sharded vs vmap", lane))
        assert_same_result(p, w, ("sharded, no carry", lane))
        assert_same_state(gs, ws, ("sharded vs vmap", lane))
    assert_lanes_own_memory(got, got_st)
    assert_lanes_own_memory(plain, [{}] * n)


FOUR_DEVICES = """
import sys
sys.path.insert(0, sys.argv[1])
import test_engine_results as T
T.check_sharded_lanes()
print("ok")
"""


def test_sharded_lanes_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR_DEVICES,
                        str(ROOT / "tests")], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.splitlines()[-1] == "ok"


@pytest.fixture(scope="module")
def batch():
    return E.run_batch(_spec(), SCHEMES, SEEDS, return_carry=True)


@pytest.mark.parametrize("lane", [0, 3, 5])
def test_run_matches_its_batch_lane(batch, lane):
    """``run`` builds its result and state through the same host path
    as a ``run_batch`` lane, and gets the same values."""
    scheme, seed = E.batch_lanes(SCHEMES, SEEDS)[lane]
    res, state = E.run(B.respec_scheme(_spec(), scheme), seed=seed,
                       return_carry=True)
    assert_same_result(res, batch[0][lane], lane)
    assert_same_state(state, batch[1][lane], lane)
    assert res.fct_ticks.dtype == np.int32 and res.done.dtype == bool
    assert res.fct_ticks.shape == (len(FLOWS),)
    assert np.all(res.done)


def test_batch_lanes_own_their_memory(batch):
    assert_lanes_own_memory(*batch)
