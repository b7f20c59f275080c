"""Trip-count-aware HLO cost model.

XLA's ``compiled.cost_analysis()`` counts each while-loop body ONCE, which
undercounts layer-scanned models by ~num_layers.  This module parses the
post-SPMD HLO text, resolves ``known_trip_count`` from backend_config, and
walks the call graph multiplying loop bodies by their trip counts.

Costs:
  * FLOPs        — dot ops: 2 * prod(result dims) * prod(lhs contracting
                   dims); convolutions: 2 * prod(result) * prod(kernel
                   spatial) * Cin (approx).
  * bytes        — per top-level op: operand bytes + result bytes (fusion
                   bodies are NOT walked for bytes: a fusion is one HBM
                   round-trip, which matches TPU semantics).  Free ops
                   (bitcast, tuple plumbing, parameter, constant) excluded.
  * collectives  — bytes by kind (all-gather / all-reduce / reduce-scatter /
                   all-to-all / collective-permute), result-shape sized.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "token": 0, "f8e4m3fn": 1, "f8e5m2": 1,
}

_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_ATOM = re.compile(r"(\w+)\[([\d,]*)\]")
# `%name = ` prefix; the shape + kind are tokenized by _split_op_line (tuple
# shapes contain spaces, parens and even '=' inside /*index=k*/ comments,
# so a single regex cannot cut them reliably).
_OP_ASSIGN = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
_KIND_CALL = re.compile(r"^([\w\-]+)\((.*)$", re.S)


def _split_op_line(line: str):
    """'%n = SHAPE kind(args...' -> (name, shape, kind, args) or None."""
    m = _OP_ASSIGN.match(line)
    if not m:
        return None
    name, rest = m.groups()
    if rest.startswith("("):
        depth = 0
        end = -1
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        if end < 0:
            return None
        shape, tail = rest[: end + 1], rest[end + 1:].lstrip()
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        shape, tail = rest[:sp], rest[sp + 1:].lstrip()
    km = _KIND_CALL.match(tail)
    if not km:
        return None
    kind, args = km.groups()
    return name, shape, kind, args
# Computation headers: `%region_0.24 (arg: (bf16[2,3], s32[])) -> (...) {`
# Param lists may contain nested parens (tuple types), so match greedily to
# the ``->`` arrow rather than the first ')'.
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->")


def _atom_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def shape_str_bytes(s: str) -> int:
    return sum(_atom_bytes(dt, dims) for dt, dims in _SHAPE_ATOM.findall(s))


def shape_str_dims(s: str) -> List[int]:
    m = _SHAPE_ATOM.search(s)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class HloOp:
    name: str
    shape: str
    kind: str
    rest: str           # everything after the opening paren
    operands: List[str]
    calls: List[Tuple[str, str]]  # (role, computation) role in {body, to_apply, ...}
    trip_count: Optional[int] = None


@dataclasses.dataclass
class HloComputation:
    name: str
    ops: List[HloOp] = dataclasses.field(default_factory=list)
    symtab: Dict[str, str] = dataclasses.field(default_factory=dict)


def parse_module(hlo: str) -> Tuple[Dict[str, HloComputation], Optional[str]]:
    comps: Dict[str, HloComputation] = {}
    entry = None
    cur: Optional[HloComputation] = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        hdr = _COMP_HDR.match(line.strip())
        if hdr and line.rstrip().endswith("{"):
            cur = HloComputation(hdr.group(2))
            comps[cur.name] = cur
            if hdr.group(1):
                entry = cur.name
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        parsed = _split_op_line(line)
        if parsed is None:
            continue
        name, shape, kind, rest = parsed
        close = rest.find(")")
        operands = re.findall(r"%([\w\.\-]+)", rest[:close] if close >= 0 else rest)
        calls = []
        for cm in re.finditer(r"(to_apply|body|condition|branch_computations|calls)=\{?%?([\w\.\-]+)", rest):
            calls.append((cm.group(1), cm.group(2)))
        # branch_computations={%a, %b}: capture extras
        bm = re.search(r"branch_computations=\{([^}]*)\}", rest)
        if bm:
            calls = [c for c in calls if c[0] != "branch_computations"]
            for nm in re.findall(r"%([\w\.\-]+)", bm.group(1)):
                calls.append(("branch_computations", nm))
        op = HloOp(name, shape.strip(), kind, rest, operands, calls)
        tc = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', rest)
        if tc:
            op.trip_count = int(tc.group(1))
        cur.ops.append(op)
        cur.symtab[name] = shape.strip()
    return comps, entry


def _dot_flops(op: HloOp, symtab: Dict[str, str]) -> float:
    res = shape_str_dims(op.shape)
    lhs_name = op.operands[0] if op.operands else None
    lhs_shape = shape_str_dims(symtab.get(lhs_name, "")) if lhs_name else []
    cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.rest)
    contract = 1
    if cm and lhs_shape:
        for i in cm.group(1).split(","):
            if i:
                idx = int(i)
                if idx < len(lhs_shape):
                    contract *= lhs_shape[idx]
    import math
    return 2.0 * math.prod(res) * contract if res else 0.0


def _conv_flops(op: HloOp, symtab: Dict[str, str]) -> float:
    import math
    res = shape_str_dims(op.shape)
    rhs_name = op.operands[1] if len(op.operands) > 1 else None
    k = shape_str_dims(symtab.get(rhs_name, "")) if rhs_name else []
    if not res or not k:
        return 0.0
    # kernel prod includes Cin*spatial*Cout; result includes Cout
    return 2.0 * math.prod(res) * math.prod(k) / (k[-1] if k else 1)


def _op_bytes(op: HloOp, symtab: Dict[str, str]) -> float:
    if op.kind in _FREE_OPS or op.kind == "while" or op.kind == "conditional" or op.kind == "call":
        return 0.0
    # Slice ops touch only the slice, not the (possibly huge, loop-carried)
    # source buffer: counting full operands would bill the stacked
    # (L, ...) scan tensors once PER ITERATION.
    if op.kind == "dynamic-slice" or op.kind == "slice":
        return 2.0 * shape_str_bytes(op.shape)        # read slice + write out
    if op.kind == "dynamic-update-slice":
        upd = symtab.get(op.operands[1]) if len(op.operands) > 1 else None
        return 2.0 * shape_str_bytes(upd) if upd else 0.0
    b = shape_str_bytes(op.shape)
    for o in op.operands:
        s = symtab.get(o)
        if s:
            b += shape_str_bytes(s)
    return float(b)


_PARAM_IDX = re.compile(r"^(\d+)")


def _fusion_bytes(op: HloOp, symtab: Dict[str, str],
                  comps: Dict[str, "HloComputation"]) -> float:
    """Fusion = one HBM round trip over its operands + result, refined by
    the fusion BODY:

    * params consumed ONLY via (dynamic-)slice ops stream the slice, not
      the whole buffer (loop-carried scan tensors read one row per trip);
    * dynamic-update-slice roots are in-place: traffic is the update slice
      (r+w), and the aliased full-size operand is skipped.
    """
    body = comps.get(op.calls[0][1]) if op.calls else None
    if body is None:
        return _op_bytes(op, symtab)
    pidx: Dict[int, str] = {}
    for bop in body.ops:
        if bop.kind == "parameter":
            m = _PARAM_IDX.match(bop.rest)
            if m:
                pidx[int(m.group(1))] = bop.name
    uses: Dict[str, list] = {}
    for bop in body.ops:
        for o in bop.operands:
            uses.setdefault(o, []).append((bop.kind, bop.shape))

    dus = [o for o in body.ops if o.kind == "dynamic-update-slice"]
    if dus:
        total = sum(2.0 * shape_str_bytes(body.symtab.get(d.operands[1], ""))
                    for d in dus if len(d.operands) > 1)
    else:
        total = float(shape_str_bytes(op.shape))       # result write
    res_b = shape_str_bytes(op.shape)
    skipped_alias = not dus
    for i, oname in enumerate(op.operands):
        s = symtab.get(oname)
        if not s:
            continue
        u = uses.get(pidx.get(i, ""), [])
        if u and all(k in ("dynamic-slice", "slice") for k, _ in u):
            total += sum(shape_str_bytes(shp) for _, shp in u)
            continue
        ob = shape_str_bytes(s)
        if not skipped_alias and ob == res_b:
            skipped_alias = True                        # in-place alias
            continue
        total += ob
    return total


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: Dict[str, float] = dataclasses.field(default_factory=dict)
    coll_ops: int = 0

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        for k, v in other.coll.items():
            self.coll[k] = self.coll.get(k, 0.0) + v * mult
        self.coll_ops += int(other.coll_ops * mult)


def analyze(hlo: str) -> Dict[str, float]:
    """Trip-count-aware whole-module cost.  Returns flat dict."""
    comps, entry = parse_module(hlo)
    memo: Dict[str, Cost] = {}

    def walk(name: str, depth: int = 0) -> Cost:
        if name in memo:
            return memo[name]
        memo[name] = Cost()  # cycle guard
        c = Cost()
        comp = comps.get(name)
        if comp is None or depth > 64:
            return c
        for op in comp.ops:
            if op.kind == "dot":
                c.flops += _dot_flops(op, comp.symtab)
            elif op.kind == "convolution":
                c.flops += _conv_flops(op, comp.symtab)
            base = op.kind.replace("-start", "").replace("-done", "")
            if base in _COLL_KINDS and not op.kind.endswith("-done"):
                c.coll[base] = c.coll.get(base, 0.0) + shape_str_bytes(op.shape)
                c.coll_ops += 1
            if op.kind == "fusion":
                c.bytes += _fusion_bytes(op, comp.symtab, comps)
                # walk fusion body for dots only (bytes counted at call site)
                sub = walk(op.calls[0][1], depth + 1) if op.calls else Cost()
                c.flops += sub.flops
                for k, v in sub.coll.items():
                    c.coll[k] = c.coll.get(k, 0.0) + v
            else:
                c.bytes += _op_bytes(op, comp.symtab)
            if op.kind == "fusion":
                pass
            elif op.kind == "while":
                body = next((nm for role, nm in op.calls if role == "body"), None)
                trips = op.trip_count or 1
                if body:
                    c.add(walk(body, depth + 1), trips)
            elif op.kind in ("call", "conditional", "custom-call", "reduce",
                             "sort", "scatter", "map", "reduce-window",
                             "select-and-scatter", "all-reduce"):
                for _role, nm in op.calls:
                    sub = walk(nm, depth + 1)
                    # reduction lambdas are trivial; still add (near-zero)
                    c.add(sub, 1.0)
        memo[name] = c
        return c

    if entry is None:
        entry = max(comps, key=lambda n: len(comps[n].ops)) if comps else None
    total = walk(entry) if entry else Cost()
    out = {
        "flops": total.flops,
        "bytes": total.bytes,
        "collective_bytes": sum(total.coll.values()),
        "collective_ops": total.coll_ops,
    }
    for k, v in total.coll.items():
        out[f"coll_{k}"] = v
    return out


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instruction_scopes(hlo: str, prefix: str = "hfl.") -> Dict[str, str]:
    """``{instruction name: scope}`` for every instruction of a compiled
    module whose ``metadata={op_name=...}`` path holds a component that
    starts with ``prefix`` (a ``jax.named_scope``); the outermost such
    component is the scope.  Instructions outside every such scope are
    left out.

    XLA gives a fusion the metadata of its root, so a fusion that crosses
    a scope boundary takes its root's scope; instructions of while bodies
    and custom calls keep the scope they were traced in."""
    comps, _ = parse_module(hlo)
    out: Dict[str, str] = {}
    for comp in comps.values():
        for op in comp.ops:
            m = _OP_NAME.search(op.rest)
            if m is None:
                continue
            scope = next((c for c in m.group(1).split("/")
                          if c.startswith(prefix)), None)
            if scope is not None:
                out[op.name] = scope
    return out
