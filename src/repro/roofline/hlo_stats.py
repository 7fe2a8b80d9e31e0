"""Trip-count-aware statistics from optimized HLO text.

``compiled.cost_analysis()`` counts a while-loop *body once* (verified in
tests/test_roofline.py), so scanned-layer models under-report FLOPs,
bytes, and collectives by ~the layer count. This module re-derives:

  * per-device matmul FLOPs (every ``dot`` op: 2 * prod(result) * contract),
  * per-device collective bytes by opcode,

by parsing the optimized HLO text into computations, building a symbol
table of instruction shapes, extracting while-loop trip counts from their
condition computations (max integer ``constant(N)``), and DFS-ing from
ENTRY with multipliers: ``body`` computations multiply by the trip count;
fusions/calls/conditionals multiply by 1.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\((.*)\)\s*->\s*.*\{\s*$")


def _shape_elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _first_shape(text: str) -> Optional[Tuple[str, int]]:
    m = _SHAPE_RE.search(text)
    if not m:
        return None
    return m.group(1), _shape_elems(m.group(2))


def _all_shapes_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt in DTYPE_BYTES:
            total += _shape_elems(dims) * DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Computation:
    name: str
    lines: List[str]


def cost_analysis_dict(compiled) -> Dict:
    """``compiled.cost_analysis()`` as a plain dict."""
    return dict(compiled.cost_analysis())


def split_computations(hlo: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry = None
    cur: Optional[Computation] = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        hdr = _COMP_HDR.match(line.strip()) if line.strip().endswith("{") else None
        if hdr and ("->" in line):
            cur = Computation(hdr.group(1), [])
            comps[cur.name] = cur
            if line.strip().startswith("ENTRY"):
                entry = cur.name
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            cur.lines.append(line.strip())
    return comps, entry


def build_symbol_table(comps: Dict[str, Computation]) -> Dict[str, Tuple[str, List[int]]]:
    """instruction name -> (dtype, dims) from its result type."""
    table: Dict[str, Tuple[str, List[int]]] = {}
    for comp in comps.values():
        for line in comp.lines:
            m = _INSTR_RE.match(line)
            if not m:
                continue
            name, rest = m.group(1), m.group(2)
            sm = _SHAPE_RE.search(rest)
            if sm:
                dims = [int(d) for d in sm.group(2).split(",")] if sm.group(2) else []
                table[name] = (sm.group(1), dims)
        # parameters: "name = dtype[dims] parameter(i)" handled above
    return table


def trip_count(cond: Computation) -> int:
    best = 1
    for line in cond.lines:
        for m in re.finditer(r"constant\((\d+)\)", line):
            best = max(best, int(m.group(1)))
    return best


_CALL_RE = re.compile(
    r"(?:condition|body|calls|to_apply|true_computation|false_computation)=%?([\w\.\-]+)"
)
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def dot_flops_line(line: str, table) -> int:
    """FLOPs of a dot instruction: 2 * prod(result dims) * contract size."""
    m = _INSTR_RE.match(line)
    if not m or " dot(" not in line:
        return 0
    rest = m.group(2)
    sm = _SHAPE_RE.search(rest)
    if not sm:
        return 0
    result = _shape_elems(sm.group(2))
    cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
    contract = 1
    lhs_dims = _dot_lhs_dims(line, table)
    if lhs_dims is not None and cm:
        for d in cm.group(1).split(","):
            if d != "" and int(d) < len(lhs_dims):
                contract *= lhs_dims[int(d)]
    return 2 * result * contract


def _dot_lhs_dims(line: str, table) -> Optional[List[int]]:
    """Dims of a dot's lhs operand.

    Current XLA prints typed operands -- ``dot(f32[64,32]{1,0} %a, ...)`` --
    so the lhs shape is read straight off the operand text (naive comma
    splitting breaks on the ``{1,0}`` layout braces). Older untyped operand
    lists -- ``dot(a, b)`` -- fall back to the symbol table.
    """
    ops = re.findall(r"dot\(([^)]*)\)", line)
    if not ops:
        return None
    sm = _SHAPE_RE.search(ops[0])
    if sm:  # typed operand: first shape in the operand list is the lhs type
        return [int(d) for d in sm.group(2).split(",")] if sm.group(2) else []
    names = re.findall(r"%?([\w\.\-]+)", ops[0].split(",")[0])
    if names and names[-1] in table:
        return table[names[-1]][1]
    return None


def analyze(hlo: str) -> Dict:
    comps, entry = split_computations(hlo)
    table = build_symbol_table(comps)

    # per-computation local stats + edges
    local: Dict[str, Dict] = {}
    for name, comp in comps.items():
        flops = 0
        coll = {c: 0 for c in COLLECTIVES}
        edges: List[Tuple[str, str]] = []  # (callee, kind)
        for line in comp.lines:
            if " dot(" in line:
                flops += dot_flops_line(line, table)
            for c in COLLECTIVES:
                if re.search(rf"\s{c}(-start)?\(", line) and "-done" not in line.split("=")[0]:
                    m = _INSTR_RE.match(line)
                    if m:
                        lhs_type = m.group(2).split(c)[0]
                        coll[c] += _all_shapes_bytes(lhs_type)
            if "while(" in line:
                body = cond = None
                for callee in _CALL_RE.finditer(line):
                    tgt = callee.group(1)
                    key = callee.group(0).split("=")[0]
                    if key == "body":
                        body = tgt
                    elif key == "condition":
                        cond = tgt
                if body:
                    trips = trip_count(comps[cond]) if cond and cond in comps else 1
                    edges.append((body, f"while:{trips}"))
            else:
                for callee in _CALL_RE.finditer(line):
                    key = callee.group(0).split("=")[0]
                    if key in ("calls", "to_apply", "true_computation", "false_computation"):
                        edges.append((callee.group(1), "call"))
                bm = _BRANCH_RE.search(line)
                if bm:
                    for b in bm.group(1).split(","):
                        edges.append((b.strip().lstrip("%"), "call"))
        local[name] = dict(flops=flops, coll=coll, edges=edges)

    # DFS with multipliers (memoized on (comp, multiplier) is wrong for
    # shared comps under different trips -- recompute per path; graphs are
    # small, recursion fine)
    import sys

    sys.setrecursionlimit(10_000)
    total = dict(flops=0, coll={c: 0 for c in COLLECTIVES}, while_trips=[])

    seen_stack = set()

    def walk(name: str, mult: int):
        if name not in local or name in seen_stack:
            return
        seen_stack.add(name)
        st = local[name]
        total["flops"] += st["flops"] * mult
        for c in COLLECTIVES:
            total["coll"][c] += st["coll"][c] * mult
        for callee, kind in st["edges"]:
            if kind.startswith("while:"):
                trips = int(kind.split(":")[1])
                total["while_trips"].append(trips)
                walk(callee, mult * trips)
            else:
                walk(callee, mult)
        seen_stack.discard(name)

    if entry:
        walk(entry, 1)
    total["coll_total"] = int(sum(total["coll"].values()))
    return total
