"""Trip-count-expanded cost analysis from optimized HLO text.

XLA's ``compiled.cost_analysis()`` counts a ``while`` body ONCE, so a
scan-over-layers program under-reports FLOPs/bytes/collectives by ~n_layers
(verified empirically: a 7-trip scan of a 128³ matmul reports 1/7 the
FLOPs). This module parses the optimized HLO, builds the call graph
(entry → while bodies/conditions → fusions), reads each while op's
``known_trip_count`` backend config, and accumulates:

* ``dot_flops`` — 2·prod(result)·prod(contracted) per dot, anywhere
  (including inside fusions), multiplied down the call chain;
* ``bytes`` — operand+result bytes of *top-level* ops per computation
  (fusion internals excluded: a fusion is one kernel, its internals stay in
  registers/VMEM — matching how "bytes accessed" should count HBM);
* ``collective_bytes`` — per kind, trip-expanded.

This is the §Roofline accounting; raw cost_analysis numbers are kept in the
dry-run JSON for comparison.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

_DT = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
       "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
       "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_CALLED_RE = re.compile(
    r"(?:to_apply|body|condition|calls|branch_computations)=\{?%?([\w\.\-]+)"
    r"(?:, ?%?([\w\.\-]+))*\}?")
_TRIP_RE = re.compile(r"known_trip_count[^0-9]*(\d+)")


def _shape_bytes_all(segment: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(segment):
        n = 1
        if m.group(2):
            for d in m.group(2).split(","):
                n *= int(d)
        total += n * _DT[m.group(1)]
    return total


@dataclass
class _Comp:
    name: str
    dot_flops: float = 0.0
    bytes_accessed: float = 0.0
    coll: dict = field(default_factory=lambda: defaultdict(float))
    calls: list = field(default_factory=list)   # (callee, multiplier)
    is_fusion_body: bool = False


@dataclass
class HloCost:
    dot_flops: float
    bytes_accessed: float
    collective_bytes: float
    collective_by_kind: dict


def _parse_computations(
    text: str, lhs_shapes: dict[str, tuple[int, ...]]
) -> tuple[dict[str, "_Comp"], str]:
    comps: dict[str, _Comp] = {}
    cur: _Comp | None = None
    entry = None
    fusion_bodies: set[str] = set()
    shapes: dict[str, int] = {}  # %name -> result bytes (per computation scope)

    for line in text.splitlines():
        s = line.rstrip()
        st = s.strip()
        header = re.match(r"(ENTRY\s+)?%?([\w\.\-]+)\s*\((.*)\)\s*->", st)
        if header and st.endswith("{"):
            cur = comps.setdefault(header.group(2), _Comp(header.group(2)))
            if header.group(1):
                entry = header.group(2)
            shapes = {}
            continue
        if cur is None or "=" not in st or not st.startswith("%"):
            continue
        lhs, rhs = st.split("=", 1)
        name = lhs.strip()
        out_bytes = _shape_bytes_all(rhs.split("(")[0])
        shapes[name] = out_bytes

        opm = re.search(r"^\s*(?:\(.*?\)|\S+)\s+([\w\-]+)\(", rhs)
        opcode = opm.group(1) if opm else ""

        # --- dot flops (counted even inside fusion bodies) ---
        if opcode == "dot":
            flops = _dot_flops(rhs, lhs_shapes)
            cur.dot_flops += flops

        # --- call edges ---
        trip = 1.0
        tm = _TRIP_RE.search(rhs)
        if tm:
            trip = float(tm.group(1))
        cm = re.search(r"body=%?([\w\.\-]+)", rhs)
        if cm:
            cur.calls.append((cm.group(1), trip))
        cm = re.search(r"condition=%?([\w\.\-]+)", rhs)
        if cm:
            cur.calls.append((cm.group(1), trip))
        cm = re.search(r"to_apply=%?([\w\.\-]+)", rhs)
        if cm and opcode not in ("reduce", "all-reduce", "reduce-scatter",
                                 "reduce-window", "scatter", "sort", "map",
                                 "select-and-scatter"):
            cur.calls.append((cm.group(1), 1.0))
        cm = re.search(r"calls=%?([\w\.\-]+)", rhs)
        if cm:
            fusion_bodies.add(cm.group(1))
            cur.calls.append((cm.group(1), 1.0))
        cm = re.search(r"branch_computations=\{([^}]*)\}", rhs)
        if cm:
            for b in cm.group(1).split(","):
                cur.calls.append((b.strip().lstrip("%"), 1.0))

        # --- bytes: operands (looked up by name) + result ---
        operands = re.findall(r"%([\w\.\-]+)", rhs.split("(", 1)[-1])
        in_bytes = sum(shapes.get(f"%{o}", 0) for o in operands)
        cur.bytes_accessed += out_bytes + in_bytes

        # --- collectives ---
        collm = _COLL_RE.search(rhs)
        if collm and collm.group(2) != "-done":
            cur.coll[collm.group(1)] += out_bytes

    for fb in fusion_bodies:
        if fb in comps:
            comps[fb].is_fusion_body = True
    return comps, entry


def _dot_flops(rhs: str, lhs_shapes: dict[str, tuple[int, ...]]) -> float:
    """2 * prod(result dims) * prod(contracted dims of lhs)."""
    out_m = _SHAPE_RE.search(rhs.split("dot(")[0])
    if not out_m:
        return 0.0
    out_elems = 1
    if out_m.group(2):
        for d in out_m.group(2).split(","):
            out_elems *= int(d)
    cm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rhs)
    ops = re.findall(r"%([\w\.\-]+)", rhs.split("dot(", 1)[-1])
    if not cm or not ops:
        return 2.0 * out_elems  # fallback: at least count outputs
    # need lhs dims: find its definition shape string
    lhs_shape = lhs_shapes.get(ops[0])
    if lhs_shape is None:
        return 2.0 * out_elems
    contracted = 1
    for idx in cm.group(1).split(","):
        if idx != "":
            contracted *= lhs_shape[int(idx)]
    return 2.0 * out_elems * contracted


def analyze_hlo_cost(text: str) -> HloCost:
    # pre-pass: record every instruction's dims for dot contraction lookup.
    # Local to this call — a module-global here would leak shapes across
    # analyses of different programs (reentrancy bug).
    lhs_shapes: dict[str, tuple[int, ...]] = {}
    for line in text.splitlines():
        st = line.strip()
        if not st.startswith("%") or "=" not in st:
            continue
        lhs, rhs = st.split("=", 1)
        m = _SHAPE_RE.search(rhs.split("(")[0])
        if m:
            dims = tuple(int(d) for d in m.group(2).split(",") if d) or ()
            lhs_shapes[lhs.strip().lstrip("%")] = dims

    comps, entry = _parse_computations(text, lhs_shapes)
    if entry is None:
        return HloCost(0.0, 0.0, 0.0, {})

    # accumulate multipliers over the call graph (memoized DFS)
    totals = {"flops": 0.0, "bytes": 0.0}
    coll_total: dict[str, float] = defaultdict(float)
    visiting: set[str] = set()

    def walk(name: str, mult: float):
        comp = comps.get(name)
        if comp is None or name in visiting:
            return
        visiting.add(name)
        totals["flops"] += comp.dot_flops * mult
        if not comp.is_fusion_body:
            totals["bytes"] += comp.bytes_accessed * mult
            for k, v in comp.coll.items():
                coll_total[k] += v * mult
        for callee, trip in comp.calls:
            walk(callee, mult * trip)
        visiting.discard(name)

    walk(entry, 1.0)
    return HloCost(
        dot_flops=totals["flops"],
        bytes_accessed=totals["bytes"],
        collective_bytes=float(sum(coll_total.values())),
        collective_by_kind=dict(coll_total),
    )
