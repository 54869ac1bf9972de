"""Trip-counted HLO cost model.

XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified:
a lax.scan of 8 matmuls reports 1 matmul of FLOPs), silently undercounting
every scan-over-layers model. This analyzer parses the post-SPMD HLO text,
recovers the call graph (while/fusion/call/conditional), reads loop trip
counts from XLA's ``known_trip_count`` backend config (fallback: the loop
condition's compare constant), and accumulates:

  * dot FLOPs            2 * prod(output dims) * prod(contracting dims)
  * HBM traffic bytes    output + operand bytes of executed top-level
                         instructions (fusion internals stay in VMEM;
                         fusion boundaries hit HBM)
  * collective bytes     ring cost per kind: all-reduce 2(n-1)/n, all-gather
                         /all-to-all (n-1)/n, reduce-scatter (n-1) x shard,
                         permute 1x — per device

All quantities are per-device (the HLO module is the partitioned program).
Elementwise FLOPs are ignored (dots dominate — standard MFU practice).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 0.5, "u4": 0.5,
}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
_FREE_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast", "iota",
    "after-all", "partition-id", "replica-id", "reshape", "while", "conditional", "call",
}


def _type_bytes(type_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1.0
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _first_shape_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def _elems(type_str: str) -> float:
    n = 0.0
    for _, dims in _SHAPE_RE.findall(type_str):
        e = 1.0
        for d in dims.split(","):
            if d:
                e *= int(d)
        n += e
    return n


@dataclass
class Instr:
    name: str
    op: str
    result_type: str
    operands: list[str]
    line: str


@dataclass
class Computation:
    name: str
    types: dict[str, str] = field(default_factory=dict)  # value name -> type
    instrs: list[Instr] = field(default_factory=list)


_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\((.*)\)\s*->\s*.*\{\s*$")
_INSTR_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\((.*)$")
_NAME_RE = re.compile(r"%([\w.\-]+)")


def parse_hlo(text: str) -> tuple[dict[str, Computation], str]:
    comps: dict[str, Computation] = {}
    entry = ""
    cur: Computation | None = None
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        mh = _COMP_HDR.match(line)
        if mh and line.endswith("{"):
            cur = Computation(mh.group(2))
            comps[cur.name] = cur
            if mh.group(1):
                entry = cur.name
            # header params: "name: type, name: type"
            for pm in re.finditer(r"([\w.\-]+)\s*:\s*(\([^)]*\)|\w+\[[\d,]*\](?:\{[^}]*\})?)", mh.group(3)):
                cur.types[pm.group(1)] = pm.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        mi = _INSTR_RE.match(line)
        if not mi:
            continue
        name, rtype, op, rest = mi.groups()
        # operand region: up to the first top-level ')'
        depth = 1
        end = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        args = rest[:end]
        operands = _NAME_RE.findall(args)
        cur.types[name] = rtype
        cur.instrs.append(Instr(name, op, rtype, operands, line))
    return comps, entry


def _trip_count(ins: Instr, comps: dict[str, Computation]) -> int:
    m = re.search(r'known_trip_count.{0,6}?"n":"(\d+)"', ins.line)
    if m:
        return max(int(m.group(1)), 1)
    mc = re.search(r"condition=%?([\w.\-]+)", ins.line)
    if mc and mc.group(1) in comps:
        cond = comps[mc.group(1)]
        consts = []
        for i2 in cond.instrs:
            m2 = re.search(r"constant\((\d+)\)", i2.line)
            if m2:
                consts.append(int(m2.group(1)))
        if consts:
            return max(max(consts), 1)
    return 1


def _dot_flops(ins: Instr, types: dict[str, str]) -> float:
    out_elems = _elems(ins.result_type)
    lhs_type = types.get(ins.operands[0], "") if ins.operands else ""
    lhs_dims = _first_shape_dims(lhs_type)
    k = 1.0
    mc = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.line)
    if mc and lhs_dims:
        for idx in mc.group(1).split(","):
            if idx:
                i = int(idx)
                if i < len(lhs_dims):
                    k *= lhs_dims[i]
    return 2.0 * out_elems * k


def _group_size(line: str, total_devices: int) -> int:
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return max(len([x for x in m.group(1).split(",") if x.strip() != ""]), 1)
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    return total_devices


def _collective_moved(ins: Instr, n_dev: int) -> tuple[str, float]:
    op = ins.op[: -len("-start")] if ins.op.endswith("-start") else ins.op
    if op not in _COLLECTIVES:
        return "", 0.0
    n = _group_size(ins.line, n_dev)
    payload = _type_bytes(ins.result_type)
    if op == "all-reduce":
        moved = 2.0 * (n - 1) / max(n, 1) * payload
    elif op == "all-gather":
        moved = (n - 1) / max(n, 1) * payload
    elif op == "reduce-scatter":
        moved = (n - 1) * payload
    elif op == "all-to-all":
        moved = (n - 1) / max(n, 1) * payload
    else:
        moved = payload
    return op, moved


@dataclass
class CostSummary:
    flops: float = 0.0
    bytes: float = 0.0
    collectives: dict = field(default_factory=lambda: {k: 0.0 for k in _COLLECTIVES})
    collective_counts: dict = field(default_factory=lambda: {k: 0.0 for k in _COLLECTIVES})

    @property
    def collective_total(self) -> float:
        return sum(self.collectives.values())

    def scaled(self, m: float) -> "CostSummary":
        return CostSummary(
            self.flops * m, self.bytes * m,
            {k: v * m for k, v in self.collectives.items()},
            {k: v * m for k, v in self.collective_counts.items()},
        )

    def add(self, other: "CostSummary") -> None:
        self.flops += other.flops
        self.bytes += other.bytes
        for k in _COLLECTIVES:
            self.collectives[k] += other.collectives[k]
            self.collective_counts[k] += other.collective_counts[k]


def _instr_bytes(ins: Instr, types: dict[str, str]) -> float:
    """Op-aware HBM traffic model.

    Slicing ops touch only the slice (XLA implements them as offset reads /
    in-place updates), NOT the full buffer — charging the whole operand would
    overcount a scan body by the full stacked-parameter size per iteration.
    """
    out_b = _type_bytes(ins.result_type)

    def opnd(i: int) -> float:
        if i < len(ins.operands):
            return _type_bytes(types.get(ins.operands[i], ""))
        return 0.0

    op = ins.op
    if op in ("dynamic-slice", "slice", "gather"):
        return 2.0 * out_b  # read slice + write result
    if op == "dynamic-update-slice":
        return 2.0 * opnd(1)  # read update + write into buffer (in place)
    if op == "scatter":
        upd = opnd(2) or out_b
        return 2.0 * upd
    if op in ("broadcast", "pad"):
        return out_b  # write-only (operand is small / reread from cache)
    if op == "concatenate":
        return 2.0 * out_b  # read all pieces + write result
    in_b = sum(_type_bytes(types.get(o, "")) for o in ins.operands)
    return out_b + in_b


def _fusion_operand_bytes(ins: Instr, types: dict[str, str], comps: dict[str, "Computation"]) -> float:
    """Slice-aware fusion input traffic: an operand whose in-fusion parameter
    is consumed ONLY by (dynamic-)slice/gather is read at slice granularity —
    charging the full stacked-parameter array per scan iteration would
    overcount by the layer count."""
    called = _called_comps(ins)
    fused = comps.get(called[0]) if called else None
    total = 0.0
    param_uses: dict[int, list[Instr]] = {}
    param_names: dict[int, str] = {}
    if fused is not None:
        for fi in fused.instrs:
            if fi.op == "parameter":
                m = re.search(r"parameter\((\d+)\)", fi.line)
                if m:
                    param_names[int(m.group(1))] = fi.name
        name_to_idx = {v: k for k, v in param_names.items()}
        for fi in fused.instrs:
            for o in fi.operands:
                if o in name_to_idx:
                    param_uses.setdefault(name_to_idx[o], []).append(fi)
    for i, o in enumerate(ins.operands):
        full = _type_bytes(types.get(o, ""))
        uses = param_uses.get(i)
        if uses and all(u.op in ("dynamic-slice", "slice", "gather") for u in uses):
            sliced = sum(_type_bytes(u.result_type) for u in uses)
            total += min(full, sliced)
        else:
            total += full
    return total


def _called_comps(ins: Instr) -> list[str]:
    out = []
    for key in ("calls", "to_apply", "body", "branch_computations"):
        m = re.search(key + r"=\{?%?([\w.\-]+(?:\s*,\s*%?[\w.\-]+)*)\}?", ins.line)
        if m:
            out += [c.strip().lstrip("%") for c in m.group(1).split(",")]
    return out


def analyze(text: str, n_devices: int) -> CostSummary:
    comps, entry = parse_hlo(text)
    if not entry:
        return CostSummary()
    memo: dict[tuple[str, bool], CostSummary] = {}

    def cost_of(name: str, top_level: bool, stack: frozenset) -> CostSummary:
        key = (name, top_level)
        if key in memo:
            return memo[key]
        comp = comps.get(name)
        cs = CostSummary()
        if comp is None or name in stack:
            return cs
        stk = stack | {name}
        for ins in comp.instrs:
            if ins.op == "dot":
                cs.flops += _dot_flops(ins, comp.types)
            ckind, moved = _collective_moved(ins, n_devices)
            if ckind:
                cs.collectives[ckind] += moved
                cs.collective_counts[ckind] += 1
            if ins.op == "while":
                trips = _trip_count(ins, comps)
                mb = re.search(r"body=%?([\w.\-]+)", ins.line)
                if mb:
                    cs.add(cost_of(mb.group(1), True, stk).scaled(trips))
            elif ins.op == "call":
                # a call body executes at the caller's level: its instructions
                # materialize to HBM exactly as if inlined (XLA:CPU wraps
                # parallelized elementwise ops in %parallel_* calls), so bytes
                # count — unlike fusion internals, which stay in VMEM/registers
                for cn in _called_comps(ins):
                    if cn in comps:
                        cs.add(cost_of(cn, top_level, stk))
            elif ins.op in ("fusion", "conditional") or _called_comps(ins):
                for cn in _called_comps(ins):
                    if cn in comps:
                        sub = cost_of(cn, False, stk)
                        # fusion internals: flops + collectives count; bytes don't
                        cs.flops += sub.flops
                        for k in _COLLECTIVES:
                            cs.collectives[k] += sub.collectives[k]
                            cs.collective_counts[k] += sub.collective_counts[k]
            # HBM bytes: only executed, materializing instructions
            if top_level and ins.op not in _FREE_OPS:
                if ins.op == "fusion":
                    cs.bytes += _type_bytes(ins.result_type) + _fusion_operand_bytes(ins, comp.types, comps)
                else:
                    cs.bytes += _instr_bytes(ins, comp.types)
        memo[key] = cs
        return cs

    return cost_of(entry, True, frozenset())


def per_collective_sites(text: str, n_devices: int, top: int = 12) -> list[tuple[str, float, float]]:
    """(kind + payload type + metadata hint, trip-weighted bytes, executions)."""
    comps, entry = parse_hlo(text)
    sites: dict[str, list[float]] = {}

    def walk(name: str, mult: float, stack: frozenset):
        comp = comps.get(name)
        if comp is None or name in stack:
            return
        stk = stack | {name}
        for ins in comp.instrs:
            ckind, moved = _collective_moved(ins, n_devices)
            if ckind:
                mo = re.search(r'op_name="([^"]*)"', ins.line)
                hint = mo.group(1)[-60:] if mo else ""
                key = f"{ckind} {ins.result_type.split('{')[0]} {hint}"
                sites.setdefault(key, [0.0, 0.0])
                sites[key][0] += moved * mult
                sites[key][1] += mult
            if ins.op == "while":
                trips = _trip_count(ins, comps)
                mb = re.search(r"body=%?([\w.\-]+)", ins.line)
                if mb:
                    walk(mb.group(1), mult * trips, stk)
            else:
                for cn in _called_comps(ins):
                    walk(cn, mult, stk)

    if entry:
        walk(entry, 1.0, frozenset())
    rows = [(k, v[0], v[1]) for k, v in sites.items()]
    rows.sort(key=lambda r: -r[1])
    return rows[:top]


def per_bytes_sites(text: str, top: int = 14) -> list[tuple[str, float, float]]:
    """Top HBM-traffic sites: (op + result type + op_name hint,
    trip-weighted bytes, executions). The §Perf profiling instrument."""
    comps, entry = parse_hlo(text)
    sites: dict[str, list[float]] = {}

    def walk(name: str, mult: float, stack: frozenset):
        comp = comps.get(name)
        if comp is None or name in stack:
            return
        stk = stack | {name}
        for ins in comp.instrs:
            if ins.op not in _FREE_OPS:
                if ins.op == "fusion":
                    b = _type_bytes(ins.result_type) + _fusion_operand_bytes(ins, comp.types, comps)
                else:
                    b = _instr_bytes(ins, comp.types)
                if b * mult > 0:
                    mo = re.search(r'op_name="([^"]*)"', ins.line)
                    hint = mo.group(1)[-70:] if mo else ""
                    key = f"{ins.op} {ins.result_type.split('{')[0][:46]} {hint}"
                    sites.setdefault(key, [0.0, 0.0])
                    sites[key][0] += b * mult
                    sites[key][1] += mult
            if ins.op == "while":
                trips = _trip_count(ins, comps)
                mb = re.search(r"body=%?([\w.\-]+)", ins.line)
                if mb:
                    walk(mb.group(1), mult * trips, stk)
            elif ins.op == "call":  # call bodies materialize (see analyze())
                for cn in _called_comps(ins):
                    walk(cn, mult, stk)

    if entry:
        walk(entry, 1.0, frozenset())
    rows = [(k, v[0], v[1]) for k, v in sites.items()]
    rows.sort(key=lambda r: -r[1])
    return rows[:top]
