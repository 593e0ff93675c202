"""Reduction of one profiler trace to the program's own spans and scopes.

    python3 bench/program_trace.py <trace dir> [--chips N]

reads the newest ``.xplane.pb`` under a directory that ``jax.profiler``
wrote around one or more ``engine.run_batch`` calls (the benchmark's
``bench.sweep`` spans, or else the engine's ``spritz.run_batch`` spans,
bound the window), on the device planes' clock, and prints three tables:

- device time per chip under each ``spritz.*`` scope, in ms per horizon
  step: every leaf operation of a chip's ``XLA Ops`` line inside that
  chip's batched loop goes to the innermost ``spritz.*`` scope in the
  HLO metadata of its instruction (a fusion that lost its own takes the
  scope most of its fused instructions carry); operations of the loop
  with none go under ``unscoped``, operations outside it under
  ``outside_loop``;
- device idle per chip by cause, an exact partition of the idle time
  ``tracing.py`` finds, each part under the first of: ``device_loop``
  (inside that chip's loop), ``chip_wait`` (after that chip's loop ended
  while another chip's loop of the same sweep still runs), the innermost
  ``spritz.*`` host span covering it, ``bench`` (inside a sweep span
  only) and ``outside_sweeps``;
- per sweep, the ``spritz.*`` host spans with their durations and
  arguments;

and four numbers: ``tick_enqueue_ms`` and ``tick_policy_ms`` (the
busiest chip's device ms per step under ``spritz.E_enqueue``, and under
the two policy scopes), ``lane_assembly_ms`` (mean ``spritz.assemble_
lanes``) and ``result_copy_ms`` (mean part of ``spritz.results`` after the
last chip's loop ended).  The batched loop on a chip is an outermost
``while`` of its ``XLA Ops`` line.  A program without the spans and
scopes reduces to empty span tables and all loop time ``unscoped``.
"""
from __future__ import annotations

import argparse
import bisect
import re
import sys
from collections import Counter, defaultdict
from pathlib import Path

import tracing
from tracing import DEVICE_PLANE, OPS_LINE, gaps, innermost, leaves, union

PREFIX = "spritz."
BENCH = "bench."
SWEEP = "bench.sweep"
CALL = "spritz.run_batch"
POLICY = ("spritz.policy_feedback", "spritz.policy_send")
_PROGRAM = re.compile(r"\((\d+)\)$")


def scope_of(op_name: str | None) -> str | None:
    """The innermost ``spritz.*`` scope in an ``op_name`` path."""
    parts = [p for p in (op_name or "").split("/") if p.startswith(PREFIX)]
    return parts[-1] if parts else None


def is_while(name: str) -> bool:
    return name.split(" = ")[0].lstrip("%").startswith("while")


def outer_loops(ops) -> list[tuple[float, float]]:
    """(start, end) of the ``while`` operations no other operation holds."""
    out: list[tuple[float, float]] = []
    for s, e, n, _ in sorted(ops, key=lambda o: (o[0], -(o[1] - o[0]))):
        if out and s < out[-1][1]:
            continue
        if is_while(n):
            out.append((s, e))
    return out


def _partition(pieces, labels) -> dict:
    """Seconds of ``pieces`` (sorted, disjoint) under each label;
    ``labels`` is [(start, end, label)], sorted and disjoint."""
    starts = [s for s, _, _ in labels]
    out: dict = defaultdict(float)
    for s, e in pieces:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(labels) and labels[i][0] < e:
            ls, le, lab = labels[i]
            ov = min(e, le) - max(s, ls)
            if ov > 0:
                out[lab] += ov * 1e-9
            i += 1
    return out


def _labels(lo, hi, loops, waits, spans, sweeps) -> list:
    """The window cut at every boundary, each cut labelled by the first
    cause that covers it."""
    cuts = sorted({lo, hi, *(t for iv in (*loops, *waits, *sweeps)
                             for t in iv),
                   *(t for s, e, _, _ in spans for t in (s, e))})
    cuts = [t for t in cuts if lo <= t <= hi]

    def within(ivs, t):
        return any(s <= t <= e for s, e in ivs)

    mids = [(a + b) / 2 for a, b in zip(cuts, cuts[1:])]
    inner = innermost([sp[:3] for sp in spans], mids)
    out = []
    for a, b, m, span in zip(cuts, cuts[1:], mids, inner):
        if within(loops, m):
            lab = "device_loop"
        elif within(waits, m):
            lab = "chip_wait"
        else:
            lab = span or ("bench" if within(sweeps, m) else "outside_sweeps")
        out.append((a, b, lab))
    return out


def reduce_program(host_spans, device_ops, n_devices: int) -> dict:
    """``host_spans``: (start_ns, end_ns, name, args) of the ``bench.*``
    and ``spritz.*`` host spans; ``device_ops``: {chip: [(start_ns,
    end_ns, HLO name, scope or None), ...]} of each chip's ``XLA Ops``
    line."""
    kind = SWEEP if any(n == SWEEP for _, _, n, _ in host_spans) else CALL
    sweeps = sorted((s, e) for s, e, n, _ in host_spans if n == kind)
    if not sweeps:
        raise ValueError("the trace holds no bench.sweep or spritz.run_batch span")
    lo, hi = sweeps[0][0], max(e for _, e in sweeps)
    spans = sorted((sp for sp in host_spans if sp[2].startswith(PREFIX)),
                   key=lambda sp: (sp[0], -sp[1]))
    chips = sorted(device_ops)[:n_devices]
    if len(chips) < n_devices:
        raise ValueError(f"the trace holds {len(chips)} chips, not {n_devices}")

    def sweep_of(t):
        return next((k for k, (s, e) in enumerate(sweeps) if s <= t <= e), None)

    loops = {c: [(max(s, lo), min(e, hi)) for s, e in outer_loops(device_ops[c])
                 if e > lo and s < hi] for c in chips}
    # the last loop end of each sweep over all chips
    last_end: dict = {}
    for c in chips:
        for s, e in loops[c]:
            k = sweep_of(s)
            last_end[k] = max(last_end.get(k, e), e)
    scope_s, idle_s = [], []
    for c in chips:
        ops = [(max(s, lo), min(e, hi), n, sc)
               for s, e, n, sc in leaves(device_ops[c]) if e > lo and s < hi]
        in_loop = union(loops[c])
        starts = [s for s, _ in in_loop]
        per: dict = defaultdict(float)
        for s, e, _, sc in ops:
            i = bisect.bisect_right(starts, (s + e) / 2) - 1
            inside = i >= 0 and (s + e) / 2 <= in_loop[i][1]
            per[(sc or "unscoped") if inside else "outside_loop"] += (e - s) * 1e-9
        scope_s.append(dict(per))
        waits = [(e, last_end[sweep_of(s)]) for s, e in loops[c]
                 if last_end[sweep_of(s)] > e]
        idle = gaps(union((s, e) for s, e, _, _ in ops), lo, hi)
        idle_s.append(dict(_partition(
            idle, _labels(lo, hi, loops[c], waits, spans, sweeps))))

    per_sweep = []
    for k, (s0, e0) in enumerate(sweeps):
        mine = [sp for sp in spans if s0 <= sp[0] and sp[1] <= e0]
        res = [(s, e, a) for s, e, n, a in mine if n == "spritz.results"]
        after = None
        if res and k in last_end:
            s, e, _ = res[0]
            after = max(0.0, e - max(s, last_end[k])) * 1e-9
        per_sweep.append({
            "spans": [[n, (e - s) * 1e-9, args] for s, e, n, args in mine],
            "assemble_lanes_s": next((
                (e - s) * 1e-9 for s, e, n, _ in mine
                if n == "spritz.assemble_lanes"), None),
            "results_after_loops_s": after,
            "steps": res[0][2].get("steps") if res else None})
    return {"window_s": (hi - lo) * 1e-9, "scope_s": scope_s,
            "idle_s": idle_s, "sweeps": per_sweep}


# --------------------------------------------------------------- reading
# The parts of the profiler's XSpace (tsl/profiler/protobuf/xplane.proto)
# and of XLA's HloProto (xla/service/hlo.proto) this reduction reads, by
# their field numbers: the device ops' scopes sit in the HLO metadata that
# the trace's ``/host:metadata`` plane carries, which ``ProfileData`` does
# not expose.
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "XPlane.EventMetadataEntry", True),
               ("stat_metadata", 5, "XPlane.StatMetadataEntry", True)],
    "XPlane.EventMetadataEntry": [("key", 1, "int64", False),
                                  ("value", 2, "XEventMetadata", False)],
    "XPlane.StatMetadataEntry": [("key", 1, "int64", False),
                                 ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, "string", False), ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False), ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False), ("stats", 4, "XStat", True)],
    "XStat": [("metadata_id", 1, "int64", False), ("double_value", 2, "double", False),
              ("uint64_value", 3, "uint64", False), ("int64_value", 4, "int64", False),
              ("str_value", 5, "string", False), ("bytes_value", 6, "bytes", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadata": [("name", 2, "string", False), ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string", False)],
    "HloProto": [("hlo_module", 1, "HloModuleProto", False)],
    "HloModuleProto": [("computations", 3, "HloComputationProto", True)],
    "HloComputationProto": [("instructions", 2, "HloInstructionProto", True),
                            ("id", 5, "int64", False)],
    "HloInstructionProto": [("name", 1, "string", False),
                            ("metadata", 7, "OpMetadata", False),
                            ("called_computation_ids", 38, "int64", True)],
    "OpMetadata": [("op_name", 2, "string", False)],
}
_CLASSES: dict = {}


def _messages() -> dict:
    """Message classes of ``_SCHEMA``, built once."""
    if not _CLASSES:
        from google.protobuf import descriptor_pb2, descriptor_pool
        from google.protobuf import message_factory
        F = descriptor_pb2.FieldDescriptorProto
        fdp = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                                 package="bench_xspace")
        made: dict = {}
        for full, fields in _SCHEMA.items():
            outer, _, inner = full.partition(".")
            msg = (made[outer].nested_type.add(name=inner) if inner
                   else fdp.message_type.add(name=outer))
            msg.options.map_entry = bool(inner)
            made[full] = msg
            for name, num, typ, rep in fields:
                f = msg.field.add(name=name, number=num, label=(
                    F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL))
                if typ in _SCHEMA:
                    f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xspace.{typ}"
                else:
                    f.type = getattr(F, "TYPE_" + typ.upper())
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fdp)
        _CLASSES.update({n: message_factory.GetMessageClass(
            pool.FindMessageTypeByName(f"bench_xspace.{n}"))
            for n in _SCHEMA if "." not in n})
    return _CLASSES


def _stats(stats, names: dict) -> dict:
    """{name: value} of XStats; a reference value names a stat."""
    out = {}
    for st in stats:
        v = names.get(st.ref_value) if st.HasField("ref_value") else next(
            (getattr(st, f) for f in ("int64_value", "uint64_value",
                                      "str_value", "double_value")
             if st.HasField(f)), None)
        out[names.get(st.metadata_id, "")] = v
    return out


def hlo_scopes(hlo_bytes: bytes) -> dict:
    """{instruction name: innermost ``spritz.*`` scope} of one module: an
    instruction's own ``op_name``, or else the scope most of the
    instructions it calls (a fusion's body) carry."""
    mod = _messages()["HloProto"].FromString(hlo_bytes).hlo_module
    comps = {c.id: c for c in mod.computations}
    memo: dict = {}

    def called(cid) -> list:
        if cid not in memo:
            memo[cid] = []
            for i in comps[cid].instructions if cid in comps else ():
                s = scope_of(i.metadata.op_name)
                memo[cid] += [s] if s else []
                for sub in i.called_computation_ids:
                    memo[cid] += called(sub)
        return memo[cid]

    out = {}
    for c in mod.computations:
        for i in c.instructions:
            s = scope_of(i.metadata.op_name)
            if s is None:
                inner = Counter(x for sub in i.called_computation_ids
                                for x in called(sub))
                s = inner.most_common(1)[0][0] if inner else None
            if s:
                out[i.name] = s
    return out


def _interval(t0: int, ev) -> tuple[float, float]:
    """(start_ns, end_ns) of an event, in whole ns as ``ProfileData`` (and
    so ``tracing.py``) gives them."""
    start = float(t0 + ev.offset_ps // 1000)
    return start, start + ev.duration_ps // 1000


def read_events(path: Path):
    """(host spans with their arguments, device ops with their scopes,
    host runtime events) from one ``.xplane.pb``: spans are (start_ns,
    end_ns, name, args) of the ``bench.*`` and ``spritz.*`` host events,
    ops {chip: [(start_ns, end_ns, HLO text, scope or None)]} of each
    chip's ``XLA Ops`` line, and runtime events (start_ns, end_ns, name)
    the other events of the host lines that hold a ``bench.*`` span,
    ``$``-named Python frames left out (``tracing.reduce_events``' three
    inputs are these, cut to three fields)."""
    space = _messages()["XSpace"].FromString(Path(path).read_bytes())
    scopes: dict = {}            # program id -> {instruction: scope}
    for plane in space.planes:
        if plane.name == "/host:metadata":
            for md in plane.event_metadata.values():
                m = _PROGRAM.search(md.name)
                if m and md.stats:
                    scopes[int(m.group(1))] = hlo_scopes(md.stats[0].bytes_value)
    spans, ops, runtime = [], {}, []
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = plane.event_metadata
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            t0 = line.timestamp_ns
            if dev and line.name == OPS_LINE:
                where = {}
                for k, md in meta.items():
                    st = _stats(md.stats, names)
                    prog = scopes.get(int(st.get("program_id") or 0), {})
                    where[k] = (md.name, prog.get(
                        md.name.split(" = ")[0].lstrip("%")))
                ops[int(dev.group(1))] = [
                    (*_interval(t0, ev), *where[ev.metadata_id])
                    for ev in line.events]
            elif plane.name.startswith("/host:"):
                evs = [(*_interval(t0, ev), meta[ev.metadata_id].name, ev)
                       for ev in line.events]
                spans += [(s, e, n, _stats(ev.stats, names))
                          for s, e, n, ev in evs if n.startswith((PREFIX, BENCH))]
                if any(n.startswith(BENCH) for _, _, n, _ in evs):
                    runtime += [(s, e, n) for s, e, n, _ in evs
                                if not n.startswith((BENCH, "$"))]
    return spans, ops, runtime


def read_trace(trace_dir: Path):
    """``read_events`` of the newest trace under ``trace_dir``."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise ValueError(f"no profiler trace under {trace_dir}")
    return read_events(files[-1])


def reduce_trace(trace_dir: Path, n_devices: int) -> dict:
    """The tables of the newest trace under ``trace_dir``."""
    spans, ops, _ = read_trace(trace_dir)
    return reduce_program(spans, ops, n_devices=n_devices)


def reduce_both(trace_dir: Path, n_devices: int) -> tuple[dict, dict]:
    """``tracing.reduce_events`` and ``reduce_program`` of the newest trace
    under ``trace_dir``, from one reading of it."""
    spans, ops, runtime = read_trace(trace_dir)
    device = tracing.reduce_events(
        [sp[:3] for sp in spans if sp[2].startswith(BENCH)],
        {c: [op[:3] for op in v] for c, v in ops.items()}, n_devices, runtime)
    return device, reduce_program(spans, ops, n_devices=n_devices)


def steps(out: dict) -> int | None:
    """Horizon steps over the sweeps: each sweep's longest lane, whose
    count every lane's loop ran for (``spritz.results``' ``steps``)."""
    per = [s["steps"] for s in out["sweeps"]]
    return sum(per) if per and None not in per else None


def numbers(out: dict) -> dict:
    """The four per-layer numbers; None where the trace lacks what they
    read."""
    n = steps(out)

    def per_step(scopes):
        if not n or not any(s in c for c in out["scope_s"] for s in scopes):
            return None
        return 1e3 * max(sum(c.get(s, 0.0) for s in scopes)
                         for c in out["scope_s"]) / n

    def mean_ms(key):
        v = [s[key] for s in out["sweeps"] if s[key] is not None]
        return 1e3 * sum(v) / len(v) if v else None

    return {"tick_enqueue_ms": per_step(("spritz.E_enqueue",)),
            "tick_policy_ms": per_step(POLICY),
            "lane_assembly_ms": mean_ms("assemble_lanes_s"),
            "result_copy_ms": mean_ms("results_after_loops_s")}


def report(out: dict) -> list[str]:
    """The tables and numbers as lines of text."""
    n, chips = steps(out), range(len(out["scope_s"]))
    head = " ".join(f"{'chip ' + str(c):>11s}" for c in chips)
    lines = [f"device {'ms per step' if n else 'ms'} by scope "
             f"({n or 'unknown'} steps)      {head}"]
    for k in sorted({k for c in out["scope_s"] for k in c}):
        lines.append(f"  {k:30s}" + " ".join(
            f"{1e3 * c.get(k, 0.0) / (n or 1):11.4f}" for c in out["scope_s"]))
    lines.append(f"device idle ms by cause (window {1e3 * out['window_s']:.3f}"
                 f" ms)  {head}")
    for k in sorted({k for c in out["idle_s"] for k in c}):
        lines.append(f"  {k:30s}" + " ".join(
            f"{1e3 * c.get(k, 0.0):11.3f}" for c in out["idle_s"]))
    lines.append(f"  {'idle share':30s}" + " ".join(
        f"{sum(c.values()) / out['window_s']:11.6f}" for c in out["idle_s"]))
    for k, sw in enumerate(out["sweeps"]):
        lines.append(f"sweep {k}: " + "; ".join(
            f"{name} {1e3 * d:.3f} ms {args}" for name, d, args in sw["spans"]))
    lines += [f"{k} {v}" for k, v in numbers(out).items()]
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", type=Path)
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    print("\n".join(report(reduce_trace(args.trace_dir, args.chips))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
