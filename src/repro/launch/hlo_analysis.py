"""Scan-aware HLO cost analysis for the roofline (deliverable g).

``compiled.cost_analysis()`` counts the body of a ``while`` loop exactly
once, so any model that scans over layers (all of ours do — DESIGN.md §7)
under-reports FLOPs/bytes/collectives by ~n_layers.  This module re-derives
the three roofline terms from the *post-optimization, post-SPMD* HLO text,
walking the call graph and multiplying each ``while`` body by the
``known_trip_count`` XLA records in its ``backend_config``.

Cost model (documented, deliberately simple — matmuls dominate):
  * flops: ``dot``/``convolution`` exactly (2 * prod(out) * prod(contract));
    elementwise/reduce ops at 1 flop per output element.  Fusion bodies are
    descended for flops (the dots inside count).
  * bytes (HBM traffic proxy): for every *top-level* op of a computation,
    operand bytes + output bytes.  Fusions are treated as a single op at
    their boundary (post-fusion traffic — tighter than cost_analysis's
    pre-fusion "bytes accessed").  ``parameter/constant/tuple/
    get-tuple-element/bitcast`` are free.
  * collectives: output bytes summed per op type (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute), scaled by trip counts.

The analysis is validated against an unrolled lowering (no scan => XLA's
own numbers are correct) in tests/test_hlo_analysis.py.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DT_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2,
    "u16": 2, "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8,
    "c128": 16, "token": 0, "opaque": 0,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")


# ops that move no data / are layout-only views
_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "after-all", "partition-id", "replica-id", "iota"}

# ops whose sub-computations are *applied per element* (cheap scalar lambdas)
_SCALAR_SUBCOMP_OPS = {"reduce", "reduce-window", "scatter", "map", "sort",
                       "select-and-scatter", "all-reduce", "reduce-scatter"}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_list(type_str: str) -> list[tuple[str, tuple[int, ...]]]:
    """All array shapes in a (possibly tuple) HLO type string."""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        shape = tuple(int(d) for d in dims.split(",") if d)
        out.append((dt, shape))
    return out


def _type_bytes(type_str: str) -> int:
    tot = 0
    for dt, shape in _shape_list(type_str):
        n = 1
        for d in shape:
            n *= d
        tot += n * _DT_BYTES.get(dt, 4)
    return tot


def _num_elems(type_str: str) -> int:
    tot = 0
    for _, shape in _shape_list(type_str):
        n = 1
        for d in shape:
            n *= d
        tot += n
    return tot


@dataclasses.dataclass
class Op:
    name: str
    type_str: str          # full result type (may be a tuple)
    kind: str              # "dot", "fusion", "while", "add", ...
    operands: list[str]    # referenced op names (no leading %)
    tail: str              # attribute text after the operand list
    param_idx: int = -1    # for kind == "parameter": its index


@dataclasses.dataclass
class Computation:
    name: str
    ops: list[Op]


# op-line prefix:  [ROOT] %name =
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*")
_KIND_RE = re.compile(r"\s*([a-z][a-z0-9-]*)\(")

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*(?:\([^)]*\))?.*\{\s*$")


def _split_op_line(line: str):
    """Split '[ROOT] %name = <type> kind(<operands>), attrs' robustly.

    Tuple types may contain '/*index=N*/' comments and nested parens, so
    the type is extracted with balanced-paren matching, not a regex."""
    m = _NAME_RE.match(line)
    if not m:
        return None
    name = m.group(1)
    rest = line[m.end():]
    if rest.startswith("("):  # tuple type: find matching close paren
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    type_str, rest = rest[:i + 1], rest[i + 1:]
                    break
        else:
            return None
    else:
        tm = re.match(r"([a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)", rest)
        if not tm:
            return None
        type_str, rest = tm.group(1), rest[tm.end():]
    km = _KIND_RE.match(rest)
    if not km:
        return None
    kind = km.group(1)
    rest = rest[km.end():]
    # operand list runs to the matching close paren of 'kind('
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    operand_str, tail = rest[:end], rest[end + 1:]
    operands = re.findall(r"%([^\s,()]+)", operand_str)
    pidx = -1
    if kind == "parameter":
        try:
            pidx = int(operand_str.strip())
        except ValueError:
            pass
    return name, type_str, kind, operands, tail, pidx


def parse_hlo(text: str) -> dict[str, Computation]:
    """Parse computations and their op lists from HLO text."""
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in text.splitlines():
        stripped = line.strip()
        if cur is None:
            if stripped.endswith("{") and ("=" not in stripped.split("(")[0]):
                m = _COMP_RE.match(stripped)
                if m:
                    cur = Computation(m.group(1), [])
            continue
        if stripped == "}":
            comps[cur.name] = cur
            cur = None
            continue
        parts = _split_op_line(line)
        if parts is None:
            continue
        cur.ops.append(Op(*parts))
    return comps


def _called_computations(op: Op) -> list[str]:
    """Sub-computations invoked by an op (body/condition/calls/to_apply/...)."""
    return re.findall(
        r"(?:body|condition|calls|to_apply|branch_computations=\{)[=]?%?"
        r"([^\s,(){}]+)", op.tail)


def _trip_count(op: Op) -> int:
    m = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', op.tail)
    return int(m.group(1)) if m else 1


def _dot_flops(op: Op, shapes: dict[str, str]) -> float:
    """2 * prod(output dims) * prod(lhs contracting dims)."""
    out_elems = _num_elems(op.type_str)
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.tail)
    if not m or not op.operands:
        return 2.0 * out_elems  # scalar-ish dot
    cdims = [int(d) for d in m.group(1).split(",") if d]
    lhs_type = shapes.get(op.operands[0], "")
    sl = _shape_list(lhs_type)
    if not sl:
        return 2.0 * out_elems
    lhs_shape = sl[0][1]
    k = 1
    for d in cdims:
        if d < len(lhs_shape):
            k *= lhs_shape[d]
    return 2.0 * out_elems * k


def _conv_flops(op: Op, shapes: dict[str, str]) -> float:
    out_elems = _num_elems(op.type_str)
    if len(op.operands) < 2:
        return 2.0 * out_elems
    sl = _shape_list(shapes.get(op.operands[1], ""))
    if not sl:
        return 2.0 * out_elems
    kernel_elems = 1
    for d in sl[0][1]:
        kernel_elems *= d
    # per output element: one MAC per kernel element / output feature
    out_features = sl[0][1][-1] if sl[0][1] else 1
    return 2.0 * out_elems * max(kernel_elems // max(out_features, 1), 1)


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: dict = dataclasses.field(
        default_factory=lambda: defaultdict(float))
    coll_count: dict = dataclasses.field(
        default_factory=lambda: defaultdict(int))

    def scaled(self, k: float) -> "Cost":
        c = Cost(self.flops * k, self.bytes * k)
        for key, v in self.coll_bytes.items():
            c.coll_bytes[key] = v * k
        for key, v in self.coll_count.items():
            c.coll_count[key] = int(v * k)
        return c

    def add(self, other: "Cost") -> None:
        self.flops += other.flops
        self.bytes += other.bytes
        for key, v in other.coll_bytes.items():
            self.coll_bytes[key] += v
        for key, v in other.coll_count.items():
            self.coll_count[key] += v

    def total_coll_bytes(self) -> float:
        return float(sum(self.coll_bytes.values()))


class HloCostModel:
    """Recursive, trip-count-aware cost rollup over parsed computations."""

    def __init__(self, text: str):
        self.comps = parse_hlo(text)
        # global op-name -> type string (HLO names are module-unique)
        self.shapes: dict[str, str] = {}
        for comp in self.comps.values():
            for op in comp.ops:
                self.shapes[op.name] = op.type_str
        self._memo: dict[tuple[str, bool], Cost] = {}
        self._alias_memo: dict[str, dict] = {}
        self.entry = next((n for n in self.comps if n.startswith("main")),
                          None) or self._find_entry(text)

    # -- slice-aware fusion operand accounting -----------------------------
    # A fusion whose body dynamic-update-slices into (or dynamic-slices out
    # of) a parameter touches only the slice, not the whole buffer: XLA
    # aliases the buffer in place.  Counting the full operand would charge a
    # layer-stacked [L, ...] activation save at L x its true HBM cost.
    def _fusion_param_overrides(self, comp_name: str) -> dict:
        if comp_name in self._alias_memo:
            return self._alias_memo[comp_name]
        comp = self.comps.get(comp_name)
        over: dict[int, float] = {}
        if comp is None:
            self._alias_memo[comp_name] = over
            return over
        pidx_of = {op.name: op.param_idx for op in comp.ops
                   if op.kind == "parameter"}
        for op in comp.ops:
            if op.kind == "dynamic-update-slice" and op.operands:
                tgt = pidx_of.get(op.operands[0], -1)
                if tgt >= 0 and len(op.operands) > 1:
                    upd = _type_bytes(self.shapes.get(op.operands[1], ""))
                    over[tgt] = over.get(tgt, 0.0) + upd
            elif op.kind == "dynamic-slice" and op.operands:
                tgt = pidx_of.get(op.operands[0], -1)
                if tgt >= 0:
                    over[tgt] = over.get(tgt, 0.0) + _type_bytes(op.type_str)
        self._alias_memo[comp_name] = over
        return over

    def _op_bytes(self, op: Op) -> float:
        """HBM traffic of one top-level op (slice/alias aware)."""
        out_b = _type_bytes(op.type_str)
        if op.kind == "dynamic-slice":
            return 2.0 * out_b
        if op.kind == "dynamic-update-slice":
            upd = (_type_bytes(self.shapes.get(op.operands[1], ""))
                   if len(op.operands) > 1 else out_b)
            return 2.0 * upd
        if op.kind == "gather":
            return 2.0 * out_b
        if op.kind == "scatter":
            upd = (_type_bytes(self.shapes.get(op.operands[-1], ""))
                   if op.operands else out_b)
            return 2.0 * upd + out_b
        in_b = 0.0
        if op.kind == "fusion":
            subs = _called_computations(op)
            over = self._fusion_param_overrides(subs[0]) if subs else {}
            for i, o in enumerate(op.operands):
                full = _type_bytes(self.shapes.get(o, ""))
                if i in over:
                    in_b += min(over[i], full)
                    if over[i] < full:
                        # in-place updated buffer: output aliases it too
                        out_b = max(out_b - (full - over[i]), 0.0)
                else:
                    in_b += full
        else:
            in_b = sum(_type_bytes(self.shapes.get(o, ""))
                       for o in op.operands)
        return out_b + in_b

    @staticmethod
    def _find_entry(text: str) -> str:
        m = re.search(r"^ENTRY\s+%?([^\s(]+)", text, re.M)
        return m.group(1) if m else ""

    def cost(self, comp_name: str | None = None, *,
             inside_fusion: bool = False) -> Cost:
        name = comp_name or self.entry
        key = (name, inside_fusion)
        if key in self._memo:
            return self._memo[key]
        comp = self.comps.get(name)
        total = Cost()
        if comp is None:
            return total
        self._memo[key] = total  # guards (non-existent) cycles
        for op in comp.ops:
            total.add(self._op_cost(op, inside_fusion))
        return total

    def _op_cost(self, op: Op, inside_fusion: bool) -> Cost:
        c = Cost()
        if op.kind == "dot":
            c.flops += _dot_flops(op, self.shapes)
        elif op.kind == "convolution":
            c.flops += _conv_flops(op, self.shapes)
        elif op.kind not in _FREE_OPS and op.kind not in ("while", "fusion",
                                                          "call",
                                                          "conditional"):
            # elementwise / reduce / select / ... : ~1 flop per output elem
            c.flops += _num_elems(op.type_str)

        if op.kind in COLLECTIVE_OPS:
            b = _type_bytes(op.type_str)
            c.coll_bytes[op.kind] += b
            c.coll_count[op.kind] += 1

        # ---- bytes: top-level ops only (fusion == one op at its boundary)
        if not inside_fusion and op.kind not in _FREE_OPS \
                and op.kind != "while":
            c.bytes += self._op_bytes(op)

        # ---- descend into sub-computations
        if op.kind == "while":
            body_cond = _called_computations(op)
            trips = _trip_count(op)
            for sub in body_cond:
                is_body = "body" in op.tail.split(sub)[0][-30:] or \
                          re.search(rf"body=%?{re.escape(sub)}", op.tail)
                mult = trips if is_body else min(trips, trips + 1)
                c.add(self.cost(sub, inside_fusion=inside_fusion).scaled(mult))
        elif op.kind == "fusion":
            # flops & collectives inside; bytes already counted at boundary
            c.add(self.cost(_called_computations(op)[0] if
                            _called_computations(op) else "",
                            inside_fusion=True))
        elif op.kind in ("call", "conditional", "async-start"):
            for sub in _called_computations(op):
                c.add(self.cost(sub, inside_fusion=inside_fusion))
        elif op.kind in _SCALAR_SUBCOMP_OPS:
            pass  # scalar lambda — negligible, already ~1 flop/elem above

        return c


def analyze(text: str) -> dict:
    """One-call entry: scan-corrected totals for a compiled HLO module."""
    model = HloCostModel(text)
    c = model.cost()
    return {
        "flops_corrected": c.flops,
        "bytes_corrected": c.bytes,
        "collective_bytes": {k: v for k, v in c.coll_bytes.items()},
        "collective_counts": {k: v for k, v in c.coll_count.items()},
        "collective_bytes_total": c.total_coll_bytes(),
    }


def attribute_dots(text: str, top: int = 12) -> list[dict]:
    """Per-metadata-op-name dot flops (×trip), for hillclimb hypotheses."""
    model = HloCostModel(text)
    # compute a trip multiplier per computation by walking from entry
    mult: dict[str, float] = defaultdict(float)

    def walk(name: str, k: float):
        comp = model.comps.get(name)
        if comp is None or mult[name] >= k and mult[name] > 0:
            if comp is None:
                return
        mult[name] += k
        for op in comp.ops:
            subs = _called_computations(op)
            if op.kind == "while":
                t = _trip_count(op)
                for s in subs:
                    walk(s, k * t)
            elif subs and op.kind in ("fusion", "call", "conditional"):
                for s in subs:
                    walk(s, k)

    walk(model.entry, 1.0)
    rows = defaultdict(float)
    for cname, comp in model.comps.items():
        k = mult.get(cname, 0.0)
        if k <= 0:
            continue
        for op in comp.ops:
            if op.kind not in ("dot", "convolution"):
                continue
            m = re.search(r'op_name="([^"]+)"', op.tail)
            label = m.group(1) if m else op.name
            f = (_dot_flops(op, model.shapes) if op.kind == "dot"
                 else _conv_flops(op, model.shapes))
            rows[label] += f * k
    out = [{"op": k, "flops": v} for k, v in
           sorted(rows.items(), key=lambda kv: -kv[1])]
    return out[:top]


def attribute_bytes(text: str, top: int = 15) -> list[dict]:
    """Per-op-kind (and biggest single ops) HBM-traffic attribution."""
    model = HloCostModel(text)
    mult: dict[str, float] = defaultdict(float)

    def walk(name: str, k: float):
        comp = model.comps.get(name)
        if comp is None:
            return
        mult[name] += k
        for op in comp.ops:
            subs = _called_computations(op)
            if op.kind == "while":
                t = _trip_count(op)
                for s in subs:
                    walk(s, k * t)
            elif subs and op.kind in ("call", "conditional"):
                for s in subs:
                    walk(s, k)
            # fusions NOT walked: bytes counted at the boundary

    walk(model.entry, 1.0)
    rows = defaultdict(float)
    for cname, comp in model.comps.items():
        k = mult.get(cname, 0.0)
        if k <= 0:
            continue
        for op in comp.ops:
            if op.kind in _FREE_OPS or op.kind == "while":
                continue
            m = re.search(r'op_name="([^"]+)"', op.tail)
            label = f"{op.kind}:{(m.group(1) if m else op.name)[-80:]}"
            rows[label] += model._op_bytes(op) * k
    out = [{"op": k, "bytes": v} for k, v in
           sorted(rows.items(), key=lambda kv: -kv[1])]
    return out[:top]


def attribute_collectives(text: str, top: int = 12) -> list[dict]:
    """Per-metadata-op-name collective bytes (×trip)."""
    model = HloCostModel(text)
    mult: dict[str, float] = defaultdict(float)

    def walk(name: str, k: float):
        comp = model.comps.get(name)
        if comp is None:
            return
        mult[name] += k
        for op in comp.ops:
            subs = _called_computations(op)
            if op.kind == "while":
                t = _trip_count(op)
                for s in subs:
                    walk(s, k * t)
            elif subs and op.kind in ("fusion", "call", "conditional"):
                for s in subs:
                    walk(s, k)

    walk(model.entry, 1.0)
    rows = defaultdict(float)
    for cname, comp in model.comps.items():
        k = mult.get(cname, 0.0)
        if k <= 0:
            continue
        for op in comp.ops:
            if op.kind not in COLLECTIVE_OPS:
                continue
            m = re.search(r'op_name="([^"]+)"', op.tail)
            label = f"{op.kind}:{m.group(1) if m else op.name}"
            rows[label] += _type_bytes(op.type_str) * k
    out = [{"op": k, "bytes": v} for k, v in
           sorted(rows.items(), key=lambda kv: -kv[1])]
    return out[:top]
