"""The benchmark's traffic and packet pool on the CPU: a mix's pattern is
found by name, the web-search generator draws what its mix states, and a
mix's ``pkt_slots`` sizes the program's and the reference's packet
tables alike."""
from __future__ import annotations

import functools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = BENCH / "tests" / "data"
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import harness  # noqa: E402
from reference import fabric as RF  # noqa: E402
from reference import sim as RS  # noqa: E402
from traffic import generator  # noqa: E402
from traffic import websearch as W  # noqa: E402

CONFIG_FILES = {"df72": DATA / "configs" / "df72.json",
                "df1056": BENCH / "configs" / "df1056.json",
                "sf1134": BENCH / "configs" / "sf1134.json"}
WEBSEARCH = json.loads((DATA / "traffic" / "websearch_small.json").read_text())
PERMUTATION = json.loads((BENCH / "traffic" / "permutation32.json").read_text())


def _cell(config: str, mix: dict) -> harness.Cell:
    return harness.Cell(name=f"{config}.test", chips=1,
                        config=json.loads(CONFIG_FILES[config].read_text()),
                        mix=mix, end_to_end=[], per_layer=[])


@functools.cache
def _fabric(config: str):
    return harness.reference_fabric(_cell(config, {}))


def _flows(config: str, **mix) -> dict:
    return harness.make_flows(_cell(config, dict(WEBSEARCH, **mix)),
                              _fabric(config))


def _bdp(config: str) -> int:
    return int(json.loads(CONFIG_FILES[config].read_text())["bdp_packets"])


@functools.cache
def _long_run(config: str, cap) -> dict:
    """Over 10^5 flows of uncapped (or ``cap``) web-search traffic."""
    n = _fabric(config).n_endpoints
    mean = W.mean_packets(*W.size_law(WEBSEARCH), 4096, cap)
    return _flows(config, load=0.6, size_cap_pkts=cap,
                  arrival_ticks=int(1.1e5 * mean / (0.6 * n)))


def test_patterns_are_found_by_name():
    assert {"permutation", "websearch"} <= set(generator.patterns())
    assert "generator" not in generator.patterns()
    group = np.zeros(8, np.int64)
    for bad in ("nope", "../harness", None):
        with pytest.raises(ValueError, match="unknown traffic pattern") as e:
            generator.flows({"pattern": bad}, group, {})
        assert ", ".join(generator.patterns()) in str(e.value)


@pytest.mark.parametrize("config", ["df72", "df1056"])
def test_websearch_is_fixed_by_its_seed(config):
    a, b = _flows(config), _flows(config)
    other = _flows(config, seed=2)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not all(len(a[k]) == len(other[k]) and np.array_equal(a[k], other[k])
                   for k in a)


@pytest.mark.parametrize("config", ["df72", "df1056"])
def test_websearch_flows_are_well_formed(config):
    fl = _flows(config)
    n = _fabric(config).n_endpoints
    assert {k: v.dtype for k, v in fl.items()} == dict.fromkeys(fl, np.int32)
    assert len(fl["src"]) > n // 4
    assert np.all(fl["src"] != fl["dst"])
    assert np.all((fl["dst"] >= 0) & (fl["dst"] < n) & (fl["src"] < n))
    assert np.all((fl["start"] >= 0) & (fl["start"] < WEBSEARCH["arrival_ticks"]))
    assert np.all((fl["size"] >= 1) & (fl["size"] <= WEBSEARCH["size_cap_pkts"]))
    keys = np.stack([fl["start"], fl["src"], fl["dst"]], 1).tolist()
    assert keys == sorted(keys)


@pytest.mark.parametrize("config,cap", [("df72", None), ("df1056", None),
                                        ("df1056", 1024)])
def test_websearch_offers_its_load(config, cap):
    fl = _long_run(config, cap)
    window = int(fl["start"].max()) + 1
    offered = fl["size"].sum() / (_fabric(config).n_endpoints * window)
    assert offered == pytest.approx(0.6, rel=0.05)


@pytest.mark.parametrize("config", ["df72", "df1056"])
def test_websearch_sizes_follow_the_stated_cdf(config):
    """At each point of the CDF, the share of flows no larger than its
    packet count equals the law's share of bytes up to that count."""
    size = _long_run(config, None)["size"]
    assert len(size) >= 1e5
    xs, cs = W.size_law(WEBSEARCH)
    for x in xs:
        k = int(W.packets(x, 4096))
        want = float(np.interp(k * 4096, xs, cs))
        assert np.mean(size <= k) == pytest.approx(want, abs=0.02), x


def test_websearch_mean_is_exact():
    xs, cs = W.size_law(WEBSEARCH)
    u = np.random.default_rng(0).random(1_000_000)
    for cap in (None, 1024, 64):
        draw = W.packets(np.interp(u, cs, xs), 4096, cap).mean()
        assert W.mean_packets(xs, cs, 4096, cap) == pytest.approx(draw, rel=0.01)
    assert W.mean_packets(xs, cs, 4096) == pytest.approx(418.29, abs=0.01)


def test_websearch_honours_the_size_cap():
    size = _long_run("df1056", 1024)["size"]
    assert size.max() == 1024 and np.mean(size == 1024) > 0.05
    assert _long_run("df1056", None)["size"].max() > 1024


def _most_in_progress(fl: dict, bdp: int) -> int:
    """The most flows any receiver has in progress at a flow's start."""
    end = fl["start"].astype(np.int64) + fl["size"] + bdp
    most = 0
    for d in np.unique(fl["dst"]):
        on = fl["dst"] == d
        s, e = fl["start"][on], end[on]
        most = max(most, int(np.max(np.sum((s[None, :] <= s[:, None])
                                           & (s[:, None] < e[None, :]), 1))))
    return most


@pytest.mark.parametrize("config", ["df72", "df1056"])
def test_websearch_honours_the_senders_per_receiver(config):
    kw = dict(load=0.3, arrival_ticks=4096)
    capped = _flows(config, max_senders_per_recv=2, **kw)
    free = _flows(config, max_senders_per_recv=_fabric(config).n_endpoints,
                  **kw)
    assert _most_in_progress(capped, _bdp(config)) <= 2
    assert _most_in_progress(free, _bdp(config)) > 2
    # the cap moves receivers only: no flow is dropped or resized
    for k in ("src", "start", "size"):
        assert sorted(capped[k].tolist()) == sorted(free[k].tolist())


# packet tables of permutation32 before the mix could state its pool
N_PKT = {"sf1134": 36352, "df1056": 33856}


@pytest.mark.parametrize("config,slots,want", [
    ("sf1134", None, N_PKT["sf1134"]), ("df1056", None, N_PKT["df1056"]),
    ("sf1134", 20000, 20000), ("df1056", 1 << 17, N_PKT["df1056"])])
def test_pkt_slots_size_both_sides(config, slots, want):
    mix = dict(PERMUTATION, **({} if slots is None else {"pkt_slots": slots}))
    cell = _cell(config, mix)
    fab = _fabric(config)
    fl = harness.make_flows(cell, fab)
    tables = RF.flow_tables(fab, list(zip(fl["src"].tolist(),
                                          fl["dst"].tolist())),
                            max_paths=64, w_scale=3.0, path_seed=0)
    ref = RS.sizes(tables, fl, bdp=_bdp(config), n_ticks=mix["n_ticks"],
                   n_ports=fab.n_ports, pkt_slots=harness.pkt_slots(cell))
    assert harness.program_spec(cell, fl).n_pkt == ref.n_pkt == want
