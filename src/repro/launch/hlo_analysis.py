"""Post-SPMD HLO cost analyzer with while-loop trip-count multiplication.

XLA's backend ``cost_analysis()`` counts while-loop bodies ONCE (verified in
EXPERIMENTS.md §Dry-run), which under-counts scanned programs by orders of
magnitude — our step functions nest up to four scans (microbatch × layer ×
flash-KV-block × loss-chunk). This analyzer parses the optimized
(per-partition) HLO text with a real instruction parser (symbol table per
computation, tuple shapes, operand lookup) and recursively multiplies
through while-loop trip counts, producing:

  * flops            — exact for dot (2·|out|·K from contracting dims)
  * collective_bytes — exact per collective kind (output-shape bytes)
  * hbm_bytes        — proxy: every materialized (non-fused) buffer written
                       + read once (2× output bytes)

Trip counts come from the while op's ``known_trip_count`` backend config
(present in scheduled XLA output), falling back to the loop-condition
comparison constant.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

__all__ = ["analyze_hlo", "HloCost"]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16, "u1": 1, "s1": 1,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_TRIVIAL = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "copy-start", "copy-done", "after-all", "partition-id", "replica-id",
    "iota", "copy",
}


# --------------------------------------------------------------------------
# Shapes
# --------------------------------------------------------------------------

def _parse_shape(s: str) -> Any:
    """Parse 'bf16[2,3]{1,0}' or '(s32[], f32[64,64]{1,0})' -> shape tree."""
    s = s.strip()
    if s.startswith("("):
        inner = s[1:-1] if s.endswith(")") else s[1:]
        parts, depth, cur = [], 0, []
        for ch in inner:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
            if ch == "," and depth == 0:
                parts.append("".join(cur))
                cur = []
            else:
                cur.append(ch)
        if cur:
            parts.append("".join(cur))
        return ("tuple", [_parse_shape(p) for p in parts if p.strip()])
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", s)
    if not m:
        return ("array", "s32", ())
    dt, dims = m.group(1), m.group(2)
    shape = tuple(int(d) for d in dims.split(",") if d)
    return ("array", dt, shape)


def _elems(shape: Any) -> float:
    if shape[0] == "tuple":
        return sum(_elems(s) for s in shape[1])
    n = 1.0
    for d in shape[2]:
        n *= d
    return n


def _bytes(shape: Any) -> float:
    if shape[0] == "tuple":
        return sum(_bytes(s) for s in shape[1])
    n = 1.0
    for d in shape[2]:
        n *= d
    return n * _DTYPE_BYTES.get(shape[1], 0)


# --------------------------------------------------------------------------
# Instruction parsing
# --------------------------------------------------------------------------

_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=")


@dataclasses.dataclass
class Instr:
    name: str
    shape: Any
    op: str
    operands: list[str]
    attrs: str


def _split_top(s: str) -> list[str]:
    parts, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur).strip())
    return parts


def _parse_instr(line: str) -> Instr | None:
    m = _NAME_RE.match(line)
    if not m:
        return None
    name = m.group(1)
    rest = line[line.find("=") + 1 :].strip()
    # Output shape: tuple (balanced parens) or typed array shape.
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    shape = _parse_shape(rest[: i + 1])
                    rest = rest[i + 1 :].strip()
                    break
        else:
            return None
    else:
        sm = re.match(r"([a-z0-9]+\[[0-9,]*\])(\{[^}]*\})?\s*", rest)
        if not sm:
            return None
        shape = _parse_shape(sm.group(1))
        rest = rest[sm.end() :]
    om = re.match(r"([\w\-]+)\s*\(", rest)
    if not om:
        return None
    op = om.group(1)
    # operand list: balanced parens after op name
    start = om.end() - 1
    depth = 0
    end = start
    for i in range(start, len(rest)):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    args = _split_top(rest[start + 1 : end])
    operands = []
    for a in args:
        am = re.match(r"(?:[a-z0-9]+\[[0-9,]*\]\S*\s+)?%?([\w\.\-]+)", a)
        operands.append(am.group(1) if am else a)
    attrs = rest[end + 1 :]
    return Instr(name=name, shape=shape, op=op, operands=operands, attrs=attrs)


def _split_computations(text: str) -> dict[str, list[Instr]]:
    comps: dict[str, list[Instr]] = {}
    cur: str | None = None
    for raw in text.splitlines():
        line = raw.strip()
        if cur is None:
            if line.endswith("{") and "->" in line:
                hm = _HEADER_RE.match(line)
                if hm:
                    cur = hm.group(1)
                    comps[cur] = []
            continue
        if line.startswith("}"):
            cur = None
            continue
        ins = _parse_instr(line)
        if ins is not None:
            comps[cur].append(ins)
    return comps


def _entry_name(text: str) -> str | None:
    m = re.search(r"ENTRY\s+%?([\w\.\-]+)", text)
    return m.group(1) if m else None


_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_S32_CONST_RE = re.compile(r"constant\((\d+)\)")


# --------------------------------------------------------------------------
# Cost accumulation
# --------------------------------------------------------------------------

@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: dict[str, float] = dataclasses.field(default_factory=dict)

    def __iadd__(self, other: "HloCost") -> "HloCost":
        self.flops += other.flops
        self.hbm_bytes += other.hbm_bytes
        for k, v in other.collective_bytes.items():
            self.collective_bytes[k] = self.collective_bytes.get(k, 0.0) + v
        return self

    def scaled(self, m: float) -> "HloCost":
        return HloCost(
            flops=self.flops * m,
            hbm_bytes=self.hbm_bytes * m,
            collective_bytes={k: v * m for k, v in self.collective_bytes.items()},
        )

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())


def _dot_flops(ins: Instr, symtab: dict[str, Any]) -> float:
    out_elems = _elems(ins.shape)
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.attrs)
    lhs_shape = symtab.get(ins.operands[0]) if ins.operands else None
    if not m or lhs_shape is None or lhs_shape[0] != "array":
        return 2.0 * out_elems
    k = 1.0
    dims = lhs_shape[2]
    for idx in (int(i) for i in m.group(1).split(",") if i):
        if idx < len(dims):
            k *= dims[idx]
    return 2.0 * out_elems * k


def _called(ins: Instr) -> list[str]:
    out = []
    for key in ("calls", "body", "condition", "to_apply", "branch_computations"):
        for m in re.finditer(rf"{key}=\{{?([^,\s}}]+(?:,\s*[^,\s}}]+)*)\}}?", ins.attrs):
            for name in m.group(1).split(","):
                out.append(name.strip().lstrip("%"))
    return out


def analyze_hlo(text: str) -> HloCost:
    comps = _split_computations(text)
    entry = _entry_name(text)
    if entry is None or entry not in comps:
        entry = next(iter(comps), None)
        if entry is None:
            return HloCost()

    # Symbol tables (op name -> shape) per computation, with gte resolution.
    symtabs: dict[str, dict[str, Any]] = {}
    for cname, instrs in comps.items():
        tab: dict[str, Any] = {}
        for ins in instrs:
            tab[ins.name] = ins.shape
        symtabs[cname] = tab

    memo: dict[tuple[str, bool], HloCost] = {}
    visiting: set[str] = set()

    def trip_count(ins: Instr) -> float:
        m = _TRIP_RE.search(ins.attrs)
        if m:
            return float(m.group(1))
        cm = re.search(r"condition=%?([\w\.\-]+)", ins.attrs)
        if cm and cm.group(1) in comps:
            best = 1.0
            for ci in comps[cm.group(1)]:
                if ci.op == "constant" and ci.operands:
                    try:
                        best = max(best, float(ci.operands[0]))
                    except ValueError:
                        pass
            return best
        return 1.0

    def analyze(cname: str, fused: bool) -> HloCost:
        key = (cname, fused)
        if key in memo:
            return memo[key]
        if cname in visiting or cname not in comps:
            return HloCost()
        visiting.add(cname)
        cost = HloCost()
        tab = symtabs[cname]
        for ins in comps[cname]:
            if ins.op == "while":
                trips = trip_count(ins)
                inner = HloCost()
                for sub in _called(ins):
                    inner += analyze(sub, fused)
                cost += inner.scaled(trips)
                continue
            if ins.op in ("fusion", "call", "custom-call", "reduce", "sort",
                          "map", "scatter", "select-and-scatter",
                          "reduce-window", "conditional", "all-reduce",
                          "reduce-scatter"):
                inner_fused = fused or ins.op == "fusion"
                for sub in _called(ins):
                    cost += analyze(sub, inner_fused)
            if ins.op == "dot":
                cost.flops += _dot_flops(ins, tab)
            elif ins.op == "convolution":
                cost.flops += 2.0 * _elems(ins.shape)
            if ins.op in _COLLECTIVES:
                b = _bytes(ins.shape)
                cost.collective_bytes[ins.op] = (
                    cost.collective_bytes.get(ins.op, 0.0) + b
                )
            if not fused and ins.op not in _TRIVIAL:
                if ins.op == "dot":
                    # write output + READ both operands: weight re-reads
                    # inside loops are real HBM traffic (a dot re-reading a
                    # loop-invariant weight every iteration pays every time).
                    cost.hbm_bytes += _bytes(ins.shape)
                    for opr in ins.operands[:2]:
                        oshape = tab.get(opr)
                        if oshape is not None:
                            cost.hbm_bytes += _bytes(oshape)
                elif ins.op in ("dynamic-slice", "gather"):
                    # DMA reads only the slice, not the source buffer.
                    cost.hbm_bytes += 2.0 * _bytes(ins.shape)
                else:
                    cost.hbm_bytes += 2.0 * _bytes(ins.shape)
        visiting.discard(cname)
        memo[key] = cost
        return cost

    return analyze(entry, False)
