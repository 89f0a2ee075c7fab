"""Post-SPMD HLO text analyzer: trip-count-aware FLOPs, HBM bytes, collective bytes.

Why this exists: ``compiled.cost_analysis()`` counts a ``while`` body ONCE, so any
scanned-layers model under-reports FLOPs/bytes by ~n_layers. The compiled HLO text
carries ``known_trip_count`` in the while backend_config; we propagate multipliers
through the call graph and weight every op accordingly.

Outputs per compiled module (all PER-DEVICE, since post-SPMD HLO is the per-device
program):
  - flops:            2*M*N*K dots (+ convolutions approximated) x multiplier
  - hbm_bytes:        sum of operand+result bytes of materialization-level ops
  - collective_bytes: wire bytes per device with ring cost factors
  - per-collective breakdown (op kind, shape bytes, group size, count)

Approximations (documented in EXPERIMENTS.md):
  - both conditional branches counted; reducers/fusion internals excluded from bytes
  - while condition ops counted once per trip
  - ring factors: AG/RS (n-1)/n, AR 2(n-1)/n, A2A (n-1)/n, permute 1.0
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3": 1, "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1,
    "f8e4m3fnuz": 1, "s4": 1, "u4": 1, "f4e2m1fn": 1, "f8e8m0fnu": 1,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")
# ops that do not correspond to real HBM traffic at materialization level
# (while/conditional/call bodies are charged separately; loop carries are in-place)
_NO_TRAFFIC = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast", "after-all",
    "iota", "partition-id", "replica-id", "broadcast", "reshape",
    "while", "conditional", "call", "custom-call", "optimization-barrier",
}


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str) -> Optional[Tuple[str, List[int]]]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None
    dt, dims = m.groups()
    return dt, [int(d) for d in dims.split(",")] if dims else []


@dataclass
class Op:
    name: str
    opcode: str
    type_str: str
    line: str
    operands: List[str]
    is_root: bool = False


@dataclass
class Computation:
    name: str
    ops: Dict[str, Op] = field(default_factory=dict)
    params: Dict[str, str] = field(default_factory=dict)  # param name -> type str


@dataclass
class CollectiveStat:
    kind: str
    bytes_per_call: int       # result bytes
    wire_bytes_per_call: float
    group_size: int
    count: float              # multiplier (trip-count weighted)


@dataclass
class HloAnalysis:
    flops: float
    hbm_bytes: float
    collective_wire_bytes: float
    collectives: List[CollectiveStat]
    top_traffic: List[tuple] = field(default_factory=list)   # (bytes*mult, comp, opcode, shape)

    def collective_summary(self) -> Dict[str, float]:
        agg: Dict[str, float] = defaultdict(float)
        for c in self.collectives:
            agg[c.kind] += c.wire_bytes_per_call * c.count
        return dict(agg)


_NAME_RE = re.compile(r"%?([\w.\-]+)$")
_OPCODE_RE = re.compile(r"([\w\-]+)\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\((.*?)\))?\s*->.*{\s*$")
_PARAM_RE = re.compile(r"([\w.\-]+):\s*((?:\([^)]*\)|[\w\[\],{}]+))")
_TRIP_RE = re.compile(r'known_trip_count\\?":\{\\?"n\\?":\\?"(\d+)')
_CALL_ATTR_RE = re.compile(r"(body|condition|calls|to_apply)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        stripped = line.strip()
        if not stripped:
            continue
        mc = _COMP_RE.match(stripped)
        if mc and stripped.endswith("{") and "=" not in stripped.split("(")[0]:
            cur = Computation(mc.group(1))
            comps[cur.name] = cur
            if mc.group(2):
                for pname, ptype in _PARAM_RE.findall(mc.group(2)):
                    cur.params[pname] = ptype
            continue
        if stripped == "}" or stripped.startswith("}"):
            # stay permissive: nested braces inside attrs never sit alone on a line
            cur = None if stripped == "}" else cur
            continue
        if cur is None:
            continue
        parsed = _parse_op_line(stripped)
        if parsed is None:
            continue
        name, type_str, opcode, operands_str = parsed
        operands = [o.strip().lstrip("%").split(" ")[0]
                    for o in _split_top_level(operands_str)]
        cur.ops[name] = Op(name, opcode, type_str.strip(), line, operands,
                           is_root=stripped.startswith("ROOT"))
    return comps


def _close(s: str, start: int, stop: str) -> int:
    """Index of the first ``stop`` char at bracket depth 0 from ``start``
    (``len(s)`` if none). TPU layouts nest brackets inside a type, as in
    ``f32[8,128]{1,0:T(8,128)}``."""
    depth = 0
    for i in range(start, len(s)):
        ch = s[i]
        if depth == 0 and ch in stop:
            return i
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
    return len(s)


def _parse_op_line(line: str):
    """``[ROOT] name = TYPE opcode(operands), attrs`` ->
    ``(name, type, opcode, operands)``, or None for any other line."""
    if line.startswith("ROOT "):
        line = line[5:]
    lhs, sep, rhs = line.partition(" = ")
    name = _NAME_RE.match(lhs.strip()) if sep else None
    if name is None:
        return None
    end = _close(rhs, 0, " ")
    mo = _OPCODE_RE.match(rhs, end + 1)
    if mo is None:
        return None
    close = _close(rhs, mo.end(), ")")
    return name.group(1), rhs[:end], mo.group(1), rhs[mo.end():close]


def _split_top_level(s: str) -> List[str]:
    out, depth, buf = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(buf))
            buf = []
        else:
            buf.append(ch)
    if buf:
        out.append("".join(buf))
    return [x for x in (b.strip() for b in out) if x]


def _group_size(line: str, total_devices: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return total_devices


def _wire_factor(kind: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (n - 1) / n
    if kind in ("all-gather", "all-to-all", "ragged-all-to-all"):
        return (n - 1) / n
    if kind == "reduce-scatter":
        return float(n - 1)       # relative to the (scattered) RESULT shape
    if kind == "collective-permute":
        return 1.0
    return 1.0


def analyze_hlo(text: str, total_devices: int = 1) -> HloAnalysis:
    comps = parse_hlo(text)

    # ----- call graph + multipliers ------------------------------------- #
    entry = next((c for c in comps if c.startswith("main") or "main" in c), None)
    mult: Dict[str, float] = defaultdict(float)
    mem_level: Dict[str, bool] = defaultdict(bool)
    order: List[str] = []

    def visit(cname: str, m: float, memlev: bool):
        if cname not in comps or m == 0:
            return
        mult[cname] += m
        mem_level[cname] = mem_level[cname] or memlev
        comp = comps[cname]
        for op in comp.ops.values():
            trip = 1.0
            if op.opcode == "while":
                mt = _TRIP_RE.search(op.line)
                trip = float(mt.group(1)) if mt else 1.0
            for attr, callee in _CALL_ATTR_RE.findall(op.line):
                if callee not in comps:
                    continue
                if op.opcode == "while" and attr in ("body", "condition"):
                    visit(callee, m * trip, memlev)
                elif op.opcode == "fusion" and attr == "calls":
                    visit(callee, m, False)
                elif op.opcode in ("call", "async-start") and attr in ("to_apply", "calls"):
                    visit(callee, m, memlev)
                else:  # reducers, comparators, select-scatter bodies
                    visit(callee, m, False)
            mb = _BRANCHES_RE.search(op.line)
            if mb:
                for callee in [c.strip().lstrip("%") for c in mb.group(1).split(",")]:
                    visit(callee, m, memlev)

    if entry:
        visit(entry, 1.0, True)
    else:  # fall back: treat every computation once
        for c in comps:
            mult[c] = 1.0
            mem_level[c] = True

    flops = 0.0
    hbm = 0.0
    coll_bytes = 0.0
    coll_stats: List[CollectiveStat] = []
    traffic: List[tuple] = []

    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0:
            continue
        # symbol table for operand shapes
        shapes: Dict[str, str] = dict(comp.params)
        for op in comp.ops.values():
            shapes[op.name] = op.type_str

        for op in comp.ops.values():
            # ---- FLOPs: dot / convolution (counted in ALL computations) ----
            if op.opcode == "dot":
                res = _shape_dims(op.type_str)
                lhs = shapes.get(op.operands[0]) if op.operands else None
                lhs_dims = _shape_dims(lhs) if lhs else None
                mcontr = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.line)
                if res and lhs_dims and mcontr:
                    k = 1
                    for ci in (int(x) for x in mcontr.group(1).split(",") if x):
                        if ci < len(lhs_dims[1]):
                            k *= lhs_dims[1][ci]
                    numel = 1
                    for d in res[1]:
                        numel *= d
                    flops += 2.0 * numel * k * m
            elif op.opcode == "convolution":
                res = _shape_dims(op.type_str)
                if res:
                    numel = 1
                    for d in res[1]:
                        numel *= d
                    flops += 2.0 * numel * m  # lower bound; convs are rare here

            # ---- collectives ----
            if op.opcode in _COLLECTIVES or (
                    op.opcode.endswith("-start") and op.opcode[:-6] in _COLLECTIVES):
                kind = op.opcode[:-6] if op.opcode.endswith("-start") else op.opcode
                n = _group_size(op.line, total_devices)
                b = _shape_bytes(op.type_str)
                if op.opcode.endswith("-start"):
                    b //= 2  # async start results carry (operand, result) tuples
                wire = b * _wire_factor(kind, n)
                coll_bytes += wire * m
                coll_stats.append(CollectiveStat(kind, b, wire, n, m))

            # ---- HBM traffic (materialization level only) ----
            if mem_level.get(cname) and op.opcode not in _NO_TRAFFIC \
                    and not op.opcode.endswith("-done"):
                b = _op_traffic_bytes(op, shapes, comps)
                hbm += b * m
                traffic.append((b * m, cname, op.opcode, op.type_str[:60]))

    traffic.sort(reverse=True)
    return HloAnalysis(flops, hbm, coll_bytes, coll_stats, traffic[:40])


# --------------------------------------------------------------------------- #
# Per-op HBM traffic model
# --------------------------------------------------------------------------- #
_SLICE_OPS = {"dynamic-slice", "slice", "gather"}


def _op_traffic_bytes(op: Op, shapes: Dict[str, str],
                      comps: Dict[str, Computation]) -> float:
    """Approximate HBM bytes moved by one materialization-level op.

    Slicing ops read only the slice, not the whole operand; dynamic-update-slice
    and scatter write only the update region (loop carries are donated/in-place).
    Fusion operands that are *sliced inside* the fusion are charged at the slice
    size (this is what scan-over-stacked-layer-params lowers to).
    """
    res = _shape_bytes(op.type_str)
    if op.opcode in _SLICE_OPS:
        return 2.0 * res
    if op.opcode == "dynamic-update-slice":
        upd = _shape_bytes(shapes.get(op.operands[1], "")) if len(op.operands) > 1 else 0
        return 2.0 * upd
    if op.opcode == "scatter":
        upd = _shape_bytes(shapes.get(op.operands[-1], "")) if op.operands else 0
        return 2.0 * upd + res * 0  # in-place update; indices negligible
    if op.opcode == "fusion":
        mc = re.search(r"calls=%?([\w.\-]+)", op.line)
        callee = comps.get(mc.group(1)) if mc else None
        if callee is None:
            total = float(res)
            for o in op.operands:
                total += _shape_bytes(shapes.get(o, ""))
            return total
        # result side: DUS roots write only the update region (in-place buffers)
        total = float(_fusion_result_bytes(callee, res))
        sliced = _fusion_param_slice_bytes(callee)
        for i, o in enumerate(op.operands):
            full = _shape_bytes(shapes.get(o, ""))
            total += min(full, sliced.get(i, full))
        return total
    total = float(res)
    for o in op.operands:
        total += _shape_bytes(shapes.get(o, ""))
    return total


# ops that neither move nor transform memory layout meaningfully for our model;
# ``convert`` included: XLA:CPU wraps in-place DUS in full-tensor f32<->bf16
# converts that XLA:TPU does not emit (verified pattern; see EXPERIMENTS.md).
_TRANSPARENT = {"bitcast", "reshape", "transpose", "copy", "convert"}


def _fusion_result_bytes(comp: Computation, default: int) -> int:
    root = next((o for o in comp.ops.values() if o.is_root), None)
    if root is None:
        return default
    roots = [root]
    if root.opcode == "tuple":
        roots = [comp.ops[o] for o in root.operands if o in comp.ops]
    total = 0
    for r in roots:
        # walk back through transparent wrappers to find an in-place DUS
        seen = 0
        while r.opcode in _TRANSPARENT and r.operands and r.operands[0] in comp.ops \
                and seen < 6:
            r = comp.ops[r.operands[0]]
            seen += 1
        if r.opcode == "dynamic-update-slice" and len(r.operands) > 1:
            upd = r.operands[1]
            src = comp.ops.get(upd)
            total += _shape_bytes(src.type_str if src else comp.params.get(upd, ""))
        else:
            total += _shape_bytes(r.type_str)
    return min(total, default) if total else default


def _fusion_param_slice_bytes(comp: Computation) -> Dict[int, int]:
    """Per fusion parameter: bytes actually READ when consumed only via slicing
    (dynamic-slice/slice/gather) or as the in-place buffer of dynamic-update-slice."""
    pidx: Dict[str, int] = {}
    for op in comp.ops.values():
        if op.opcode == "parameter":
            mi = re.search(r"parameter\((\d+)\)", op.line)
            if mi:
                pidx[op.name] = int(mi.group(1))
    consumers: Dict[str, List[Tuple[Op, int]]] = defaultdict(list)
    for op in comp.ops.values():
        for j, o in enumerate(op.operands):
            consumers[o].append((op, j))

    def walk(name: str, depth: int = 0):
        """Returns (ok, bytes_read): ok=True iff every use path ends in slicing."""
        if depth > 6:
            return False, 0
        total = 0
        for c, j in consumers.get(name, []):
            if c.opcode in _SLICE_OPS:
                total += _shape_bytes(c.type_str)
            elif c.opcode == "dynamic-update-slice" and j == 0:
                total += 0            # aliased in-place destination
            elif c.opcode in _TRANSPARENT:
                ok, b = walk(c.name, depth + 1)
                if not ok:
                    return False, 0
                total += b
            else:
                return False, 0
        return True, total

    out: Dict[int, int] = {}
    for pname, idx in pidx.items():
        if not consumers.get(pname):
            out[idx] = 0
            continue
        ok, b = walk(pname)
        if ok:
            out[idx] = b
    return out
