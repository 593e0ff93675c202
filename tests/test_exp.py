"""Experiment-matrix subsystem tests (DESIGN.md §13).

Covers: matrix sanity + tier enumeration against DESIGN.md §8, emitted
cell-JSON schema round-trip, content-hash cache hit/miss semantics, and
the ratio/counter guard plumbing on one real packet cell and one real
flow cell (tiny configs — the packet cell is the deterministic
compression probe so the smoke run stays seconds-scale)."""
from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path

import pytest

from repro.exp import hashing, matrix, runner
from repro.exp.spec import TIERS, Cell, validate_result

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------- matrix

def test_cell_ids_unique_and_valid():
    assert matrix.CELLS
    for cell_id, cell in matrix.CELLS.items():
        assert cell.cell_id == cell_id
        assert cell.tiers, cell_id
        assert cell.seeds, cell_id


def test_schemes_resolve_against_registry():
    from repro.net.policies import registry as REG
    known = set(REG.names())
    for cell in matrix.cells():
        for s in cell.schemes:
            assert s in known, f"{cell.cell_id}: unknown scheme {s}"
        for g in cell.guards:
            for key in ("scheme", "num", "den"):
                if g.get(key):
                    assert g[key] in known, \
                        f"{cell.cell_id}: guard names unknown scheme {g[key]}"


def test_every_design_s8_bench_in_some_tier():
    """Every module row of DESIGN.md §8 must appear as the owning bench
    of >= 1 registered cell in >= 1 tier."""
    text = (REPO / "DESIGN.md").read_text()
    s8 = text.split("## §8")[1].split("## §9")[0]
    wanted = set(re.findall(r"`bench_(\w+)`", s8))
    assert wanted, "DESIGN.md §8 table not found"
    covered = set()
    for tier in TIERS:
        covered |= matrix.benches(tier)
    missing = wanted - covered
    assert not missing, f"DESIGN.md §8 benches with no matrix cell: {missing}"


def test_smoke_tier_span():
    """The acceptance shape of the smoke tier: >= 6 cells spanning both
    engines, both topologies, and a mid-run failure plan."""
    smoke = matrix.cells("smoke")
    assert len(smoke) >= 6
    assert {c.engine for c in smoke} >= {"packet", "flow"}
    topos = {c.topology.rstrip("0123456789") for c in smoke}
    assert topos >= {"dragonfly", "slimfly"}
    assert any(c.failure in ("midrun_links", "loaded_midrun")
               for c in smoke)
    # smoke cells must all carry guards — they gate CI
    assert all(c.guards for c in smoke)


def test_workload_and_failure_builders_known():
    from repro.exp.workloads import FAILURES, WORKLOADS
    for cell in matrix.cells():
        if cell.engine == "packet":
            assert cell.workload in WORKLOADS, cell.cell_id
            assert cell.failure is None or cell.failure in FAILURES, \
                cell.cell_id
        elif cell.engine == "flow":
            assert cell.workload in ("train", "alltoall"), cell.cell_id
            assert cell.failure in (None, "loaded_midrun",
                                    "loaded_degraded", "chaos"), cell.cell_id
        elif cell.engine == "cross":
            # cross cells lower one bridge flow set onto both engines;
            # failure plans are not plumbed through the dual run yet
            assert cell.workload in ("train", "alltoall"), cell.cell_id
            assert cell.failure is None, cell.cell_id
        elif cell.engine == "openloop":
            kw = dict(cell.workload_kw)
            assert kw.get("fidelity", "flow") in ("flow", "packet"), \
                cell.cell_id
            assert len(kw.get("loads", (0.3, 0.6, 0.9))) >= 3, cell.cell_id
            assert cell.failure is None, cell.cell_id


# ------------------------------------------------------- schema + hashing

def _probe_cell(**over) -> Cell:
    base = matrix.CELLS["engine.dragonfly.probe.smoke"]
    return dataclasses.replace(base, **over) if over else base


def test_cell_hash_covers_spec_and_tree(monkeypatch):
    c1 = _probe_cell()
    c2 = _probe_cell(cell_id="engine.other", n_ticks=1 << 12)
    h1, h2 = hashing.cell_hash(c1), hashing.cell_hash(c2)
    assert h1 != h2
    assert h1 == hashing.cell_hash(c1)  # deterministic
    monkeypatch.setattr(hashing, "tree_digest", lambda root=None: "tampered")
    assert hashing.cell_hash(c1) != h1


def test_result_schema_validator_rejects_drift():
    ok = {"schema": 1, "cell_id": "x", "hash": "h",
          "device": {"platform": "cpu", "kind": "cpu", "count": 1}, "spec": {
        "engine": "packet", "topology": "d", "workload": "w",
        "schemes": [], "seeds": [0], "tiers": ["ci"], "guards": []},
        "rows": [{"scheme": "ecmp", "seed": 0}], "guards": [],
        "schemes_run": ["ecmp"], "wall_s": 0.1}
    assert validate_result(ok) == []
    assert validate_result({**ok, "schema": 99})
    bad = dict(ok)
    del bad["rows"]
    assert validate_result(bad)
    assert validate_result({**ok, "rows": [{"seed": 0}]})  # scheme missing
    assert validate_result({**ok, "guards": [{"ok": True}]})  # desc missing
    no_dev = dict(ok)
    del no_dev["device"]
    assert validate_result(no_dev)
    assert validate_result({**ok, "device": {"platform": "tpu"}})


# --------------------------------------------- runner: cache + guards

@pytest.fixture(scope="module")
def probe_run(tmp_path_factory):
    out = tmp_path_factory.mktemp("exp")
    summary = runner.run(cells=["engine.dragonfly.probe.smoke"], out=out,
                         verbose=False)
    return out, summary


def test_packet_cell_roundtrip_and_guards(probe_run):
    out, summary = probe_run
    assert summary.ok and len(summary.results) == 1
    (res,) = summary.results
    assert not res.cached
    obj = json.loads(res.path.read_text())
    assert validate_result(obj) == []
    assert obj["cell_id"] == "engine.dragonfly.probe.smoke"
    assert obj["schemes_run"] == ["ecmp"]
    # the ratio/counter plumbing fired: compression floor + baseline
    kinds = {g["kind"] for g in obj["guards"]}
    assert kinds == {"counter", "baseline"}
    assert all(g["ok"] for g in obj["guards"])


def test_cache_hit_then_invalidation(probe_run, monkeypatch):
    out, _ = probe_run
    again = runner.run(cells=["engine.dragonfly.probe.smoke"], out=out,
                       verbose=False)
    assert again.cache_hits == 1 and again.ok
    # a changed source tree (or cell spec) must invalidate: fake digest
    monkeypatch.setattr(hashing, "tree_digest", lambda root=None: "edited")
    path = out / "engine.dragonfly.probe.smoke.json"
    stored = json.loads(path.read_text())
    cell = matrix.CELLS["engine.dragonfly.probe.smoke"]
    assert hashing.cell_hash(cell) != stored["hash"]


def test_device_change_forces_cache_miss(probe_run, monkeypatch):
    """A result is keyed on the devices it ran on: the stored JSON names
    them, and the same cell on another device descriptor (say the CPU
    result asked for by a TPU run) misses the cache and re-executes."""
    out, _ = probe_run
    cell = matrix.CELLS["engine.dragonfly.probe.smoke"]
    stored = json.loads((out / f"{cell.cell_id}.json").read_text())
    assert stored["device"] == hashing.device_key()
    assert stored["device"]["platform"] == "cpu"
    calls = []

    def execute(c, schemes, verbose):
        calls.append(c.cell_id)
        return stored["rows"]

    monkeypatch.setattr(runner, "_execute", execute)
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(hashing, "device_key", lambda: tpu)
    monkeypatch.setattr(runner, "device_key", lambda: tpu)
    assert hashing.cell_hash(cell) != stored["hash"]
    other = out / "device_miss"
    other.mkdir(exist_ok=True)
    (other / f"{cell.cell_id}.json").write_text(json.dumps(stored))
    res = runner.run_cell(cell, out=other, verbose=False)
    assert not res.cached and calls == [cell.cell_id]
    assert json.loads(res.path.read_text())["device"] == tpu


def test_compile_cache_fixed_in_checkout_or_from_env(monkeypatch, tmp_path):
    """Unset, the persistent compilation cache sits at the fixed
    ``<repo>/.jax_cache``; ``JAX_COMPILATION_CACHE_DIR`` moves it there
    and nowhere else."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        fixed = hashing.repo_root() / ".jax_cache"
        assert compile_cache.cache_dir() == fixed
        assert compile_cache.enable() == fixed
        assert jax.config.jax_compilation_cache_dir == str(fixed)
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.enable() == tmp_path
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        cc.reset_cache()


def test_guard_breach_exits_nonzero(probe_run, monkeypatch):
    out, _ = probe_run
    breach = dataclasses.replace(
        _probe_cell(), cell_id="engine.probe.breach",
        guards=({"kind": "counter", "metric": "compression",
                 "op": ">=", "value": 1e9},))
    res = runner.run_cell(breach, out=out, verbose=False)
    assert not res.ok
    monkeypatch.setattr(matrix, "cells",
                        lambda tier=None, ids=None, bench=None: [breach])
    summary = runner.run(cells=["engine.probe.breach"], out=out,
                         verbose=False)
    assert summary.breaches
    with pytest.raises(SystemExit):
        runner.run(cells=["engine.probe.breach"], out=out, check=True,
                   verbose=False)


def test_flow_cell_roundtrip(tmp_path):
    cell = Cell(
        cell_id="fabric.test.tiny", figure="fabric_scale", bench="fabric",
        engine="flow", topology="dragonfly1056", scale="quick",
        workload="train", workload_kw={"n_chips": 32, "tp": 16,
                                       "shard": 1e6},
        schemes=("ecmp", "spritz_spray_w"), tiers=("ci",),
        guards=({"kind": "counter", "metric": "done_frac",
                 "op": ">=", "value": 0.99},
                {"kind": "ratio", "metric": "fct_us",
                 "num": "spritz_spray_w", "den": "ecmp",
                 "op": "<=", "value": 1.5}))
    res = runner.run_cell(cell, out=tmp_path, verbose=False)
    obj = json.loads(res.path.read_text())
    assert validate_result(obj) == []
    assert {r["scheme"] for r in obj["rows"]} == {"ecmp", "spritz_spray_w"}
    assert res.ok, [g for g in res.guards if not g["ok"]]
    # second run: cache hit with identical rows
    res2 = runner.run_cell(cell, out=tmp_path, verbose=False)
    assert res2.cached and res2.rows == res.rows


def test_runner_rejects_unknown_cell():
    with pytest.raises(KeyError):
        runner.run(cells=["no.such.cell"], verbose=False)


def test_scheme_override_derives_new_cache_key(probe_run, tmp_path):
    cell = _probe_cell()
    narrowed = cell.with_overrides(schemes=("ecmp",), scale="mid")
    assert narrowed.cell_id != cell.cell_id
    assert hashing.cell_hash(narrowed) != hashing.cell_hash(cell)
    # a schemes-only override must also never collide with the
    # registered cell's result file
    other = cell.with_overrides(schemes=("minimal",))
    assert other.cell_id != cell.cell_id
    # ... but a no-op override keeps the registered id (cache reuse)
    assert cell.with_overrides(schemes=cell.schemes).cell_id == cell.cell_id


# ---------------------------------------------------------- guard units

def test_guard_evaluators():
    from repro.exp.guards import evaluate
    rows = [{"scheme": "ecmp", "seed": 0, "fct_mean_us": 100.0,
             "down_violations": 0},
            {"scheme": "spritz_spray_w", "seed": 0, "fct_mean_us": 80.0,
             "down_violations": 0}]
    out = evaluate((
        {"kind": "counter", "metric": "down_violations", "op": "==",
         "value": 0},
        {"kind": "ratio", "metric": "fct_mean_us", "num": "spritz_spray_w",
         "den": "ecmp", "op": "<=", "value": 1.0},
        {"kind": "ratio", "metric": "fct_mean_us", "num": "ecmp",
         "den": "spritz_spray_w", "op": "<=", "value": 1.0},
    ), rows)
    assert [g["ok"] for g in out] == [True, True, False]
    assert out[1]["value"] == pytest.approx(0.8)
    # a scheme that was not part of the run -> skip (narrowed --schemes
    # runs guard only what they ran) ...
    (miss,) = evaluate(({"kind": "ratio", "metric": "fct_mean_us",
                         "num": "reps", "den": "ecmp", "op": "<=",
                         "value": 1.0},), rows)
    assert miss["ok"] and "skip" in miss["note"]
    # ... but a scheme that DID run with the metric missing/invalid is a
    # hard failure (emitter drift must not pass vacuously)
    (drift,) = evaluate(({"kind": "ratio", "metric": "nonexistent_metric",
                          "num": "spritz_spray_w", "den": "ecmp",
                          "op": "<=", "value": 1.0},), rows)
    assert not drift["ok"]


def test_guard_sentinel_and_nan_fail_not_skip():
    """Satellite regression: a scheme that RAN but whose metric column
    is the -1.0 empty-stats sentinel (or NaN) must FAIL ratio and
    baseline_schemes guards — the old behaviour silently passed."""
    from repro.exp.guards import evaluate
    rows = [{"scheme": "ecmp", "seed": 0, "fct_p99_us": 100.0,
             "fct_ratio_vs_ecmp": 1.0},
            {"scheme": "spritz_spray_w", "seed": 0, "fct_p99_us": -1.0,
             "fct_ratio_vs_ecmp": -1.0}]
    ratio = {"kind": "ratio", "metric": "fct_p99_us",
             "num": "spritz_spray_w", "den": "ecmp", "op": "<=",
             "value": 1.0}
    (g,) = evaluate((ratio,), rows)
    assert not g["ok"] and "sentinel" in g["note"]
    (g,) = evaluate((dict(ratio, metric="nan_metric"),),
                    [dict(r, nan_metric=float("nan")) for r in rows])
    assert not g["ok"]
    bs = {"kind": "baseline_schemes", "file": "BENCH_fabric.json",
          "path": "quick_cells.dragonfly1056.train.schemes",
          "metric": "fct_ratio_vs_ecmp", "tol": 0.25}
    (g,) = evaluate((bs,), rows)
    assert not g["ok"] and "sentinel" in g["note"]
    # ...but a run where NO row carries the metric at all (e.g. a
    # --schemes run without the ecmp reference) legitimately skips
    bare = [{k: v for k, v in r.items() if k != "fct_ratio_vs_ecmp"}
            for r in rows]
    (g,) = evaluate((bs,), bare)
    assert g["ok"] and "skip" in g["note"]


def test_guard_where_filter_scopes_rows():
    """``where`` scopes a guard to matching rows — the load-sweep cells
    gate one point of the curve (DESIGN.md §15)."""
    from repro.exp.guards import evaluate
    rows = [{"scheme": "ecmp", "seed": 0, "load": 0.3, "fct_p99_us": 10.0},
            {"scheme": "ecmp", "seed": 0, "load": 0.9, "fct_p99_us": 100.0},
            {"scheme": "spritz_spray_w", "seed": 0, "load": 0.3,
             "fct_p99_us": 20.0},
            {"scheme": "spritz_spray_w", "seed": 0, "load": 0.9,
             "fct_p99_us": 80.0}]
    g90 = {"kind": "ratio", "metric": "fct_p99_us",
           "num": "spritz_spray_w", "den": "ecmp", "op": "<=",
           "value": 1.0, "where": {"load": 0.9}}
    (a,) = evaluate((g90,), rows)
    assert a["ok"] and a["value"] == pytest.approx(0.8)
    assert "load=0.9" in a["desc"]
    (b,) = evaluate((dict(g90, where={"load": 0.3}),), rows)
    assert not b["ok"] and b["value"] == pytest.approx(2.0)
    (c,) = evaluate(({"kind": "counter", "metric": "fct_p99_us",
                      "op": "<=", "value": 30.0,
                      "where": {"load": 0.3}},), rows)
    assert c["ok"] and c["value"] == 20.0


def test_baseline_schemes_guard_reads_checked_in_file():
    from repro.exp.guards import evaluate
    base = json.loads((REPO / "BENCH_fabric.json").read_text())
    cellb = base["quick_cells"]["dragonfly1056"]["train"]["schemes"]
    rows = [{"scheme": "ecmp", "seed": 0,
             "done_frac": cellb["ecmp"]["done_frac"],
             "fct_ratio_vs_ecmp": 1.0}]
    (g,) = evaluate(({"kind": "baseline_schemes", "file": "BENCH_fabric.json",
                      "path": "quick_cells.dragonfly1056.train.schemes",
                      "metric": "done_frac", "abs_tol": 0.02},), rows)
    assert g["ok"]
    rows[0]["done_frac"] = cellb["ecmp"]["done_frac"] - 0.5
    (g,) = evaluate(({"kind": "baseline_schemes", "file": "BENCH_fabric.json",
                      "path": "quick_cells.dragonfly1056.train.schemes",
                      "metric": "done_frac", "abs_tol": 0.02},), rows)
    assert not g["ok"]
