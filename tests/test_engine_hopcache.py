"""The packet table's hop cache (DESIGN.md §14): ``pport``, ``pnport``
and ``pdelay`` of every queued or propagating packet equal the path
tables' entries for its current hop, at every segment boundary of a
run, on a static network, under a mid-run failure timeline and under
rate events; and the segmented run that carries the cache through
checkpoints equals the unsegmented one."""
import numpy as np
import pytest

from repro.net.sim import build as B
from repro.net.sim import engine as E
from repro.net.sim.failures import FailureSchedule, sample_links
from repro.net.sim.types import P_PROP, P_QUEUED, SPRAY_W
from repro.net.topology.dragonfly import make_dragonfly
from repro.net.workloads import synthetic

DF = make_dragonfly(4, 2, 2)
LINKS = sample_links(DF, 4, seed=3)
TIMELINES = {
    "static": None,
    "failures": FailureSchedule(DF).fail_links(30, LINKS).recover(200),
    "rates": FailureSchedule(DF).degrade_links(30, LINKS, 0.25, until=200),
}
BOUNDS = (20, 45, 90, 150)
SCHEMES = ["ecmp", "spritz_spray_w"]


def _spec(timeline):
    return B.build_spec(DF, synthetic.permutation(DF, 32, seed=1), SPRAY_W,
                        n_ticks=1 << 12, failure_plan=TIMELINES[timeline],
                        block_ticks=1024)


def _check_hop_cache(spec, state) -> int:
    """Asserts the cache of every P_QUEUED / P_PROP packet against the
    spec's tables; returns how many packets it checked."""
    m = np.isin(state["pstate"], [P_QUEUED, P_PROP])
    f, p, h = state["pflow"][m], state["ppath"][m], state["phop"][m]
    last = h == spec.path_len[f, p] - 1
    port = spec.path_ports[f, p, h]
    nxt = spec.path_ports[f, p, np.minimum(h + 1, spec.path_ports.shape[2] - 1)]
    np.testing.assert_array_equal(state["pport"][m], port)
    np.testing.assert_array_equal(state["pnport"][m],
                                  np.where(last, spec.n_ports, nxt))
    np.testing.assert_array_equal(
        state["pdelay"][m],
        np.where(last, spec.ret_ticks[f, p], spec.port_lat[port]))
    return int(m.sum())


def _assert_same_state(a: dict, b: dict):
    for k, v in a.items():
        if k == "policy":
            for fam, sub in v.items():
                for f, x in sub.items():
                    np.testing.assert_array_equal(
                        x, b["policy"][fam][f], err_msg=f"{fam}.{f}")
        elif k != "spritz":       # alias of policy["spritz"]
            np.testing.assert_array_equal(v, b[k], err_msg=k)


def _assert_same_result(a, b):
    for name in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=name)


def _segments(spec, batched):
    """(results, states) per lane at each bound, then run to the end."""
    cps, out = None, []
    for b in BOUNDS + (None,):
        if batched:
            res, st = E.run_batch(spec, schemes=SCHEMES, seeds=[0],
                                  until_tick=b, resume=cps, return_carry=True)
        else:
            r, s = E.run(spec, seed=0, until_tick=b, return_carry=True,
                         resume=cps[0] if cps else None)
            res, st = [r], [s]
        cps = [E.checkpoint(r, s) for r, s in zip(res, st)]
        out.append((res, st))
    return out


@pytest.mark.parametrize("batched", [False, True], ids=["solo", "batched"])
@pytest.mark.parametrize("timeline", list(TIMELINES))
def test_hop_cache_matches_path_tables_and_resumes(timeline, batched):
    spec = _spec(timeline)
    segs = _segments(spec, batched)
    checked = sum(_check_hop_cache(spec, s) for _, sts in segs for s in sts)
    assert checked > 0

    if batched:
        full, full_st = E.run_batch(spec, schemes=SCHEMES, seeds=[0],
                                    return_carry=True)
    else:
        r, s = E.run(spec, seed=0, return_carry=True)
        full, full_st = [r], [s]
    final, final_st = segs[-1]
    assert all(r.done.all() for r in full)
    for a, b in zip(full, final):
        _assert_same_result(a, b)
        assert a.down_violations == 0 and a.rate_violations == 0
    for a, b in zip(full_st, final_st):
        _assert_same_state(a, b)
