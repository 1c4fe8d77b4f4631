"""HLO cost engine: trip-count-aware FLOPs / bytes / collective analysis.

``compiled.cost_analysis()`` counts every while-loop body ONCE — for a
layer-scanned transformer that under-counts compute by ~num_layers x.
This module parses the optimized HLO text and walks the call graph
instead:

  * dot ops: 2 * output_elems * contraction_size exact MXU FLOPs
    (contraction size from the operand symbol table);
  * other array ops: 1 FLOP / output element (VPU estimate);
  * while: body + cond costs x trip count (parsed from the loop condition's
    compare constant — jax scans always lower to 0..N LT loops);
  * fusion/call: recurse for FLOPs; for HBM bytes the *fusion op's*
    operands + outputs are counted (internals stay in registers/VMEM),
    which is the right memory model for fused kernels;
  * collectives: bytes by kind, trip-count aware (a psum inside a scanned
    layer counts num_layers times).

This is the data source for §Roofline; `cost_analysis()` is kept as a
cross-check on the non-loop part.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from functools import lru_cache

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1, "fp8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"([a-z][a-z0-9\-]*)\(")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%([\w.\-]+)")
_COND_BODY_RE = re.compile(r"condition=%([\w.\-]+),\s*body=%([\w.\-]+)")
_CONST_INT_RE = re.compile(r"constant\((\d+)\)")
_LHS_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_LHS_BDIMS_RE = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "copy-done",
    "all-reduce-done", "all-gather-done", "collective-permute-done",
    "reshape", "broadcast", "transpose",  # layout ops: ~free on TPU or fused
}


def _shapes_in(text: str):
    return [(d, dims) for d, dims in _SHAPE_RE.findall(text)]


def _shape_elems(dims: str) -> int:
    if not dims:
        return 1
    return int(np.prod([int(x) for x in dims.split(",") if x]))


def _shape_bytes(dtype: str, dims: str) -> int:
    return _DTYPE_BYTES.get(dtype, 0) * _shape_elems(dims)


@dataclass
class Op:
    name: str
    opcode: str
    out_shapes: list  # [(dtype, dims)]
    operands: list  # names
    line: str


@dataclass
class Computation:
    name: str
    ops: list = field(default_factory=list)
    symbols: dict = field(default_factory=dict)  # name -> [(dtype, dims)]


@dataclass
class CostTotals:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: dict = field(default_factory=dict)
    collective_counts: dict = field(default_factory=dict)
    # per-op detail: (kind, payload bytes, trip multiplier) for every
    # collective, in walk order. The kind-keyed dicts above sum these;
    # the list keeps ops with the same kind separable — e.g. the §14
    # two-hop schedule's intra-edge vs cross-edge all-gathers.
    collective_ops: list = field(default_factory=list)

    def add_collective(self, kind: str, nbytes: float, mult: float):
        self.collective_bytes[kind] = (
            self.collective_bytes.get(kind, 0.0) + nbytes * mult)
        self.collective_counts[kind] = (
            self.collective_counts.get(kind, 0.0) + mult)

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())


def parse_module(hlo_text: str) -> tuple[dict, str]:
    """Parse computations. Returns ({name: Computation}, entry_name)."""
    comps: dict[str, Computation] = {}
    entry = None
    cur: Computation | None = None
    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_HDR_RE.match(line)
            if m and line.rstrip().endswith("{"):
                cur = Computation(m.group(1))
                if line.startswith("ENTRY"):
                    entry = m.group(1)
                continue
        else:
            if line.strip() == "}":
                comps[cur.name] = cur
                cur = None
                continue
            dm = _DEF_RE.match(line)
            if not dm:
                continue
            name, rhs = dm.groups()
            om = _OPCODE_RE.search(rhs)
            opcode = om.group(1) if om else ""
            type_part = rhs[: om.start()] if om else rhs
            out_shapes = _shapes_in(type_part)
            # operand names within the opcode's paren group
            operands = []
            if om:
                depth, j = 0, om.end() - 1
                start = j
                while j < len(rhs):
                    if rhs[j] == "(":
                        depth += 1
                    elif rhs[j] == ")":
                        depth -= 1
                        if depth == 0:
                            break
                    j += 1
                operands = re.findall(r"%([\w.\-]+)", rhs[start:j + 1])
            op = Op(name=name, opcode=opcode, out_shapes=out_shapes,
                    operands=operands, line=line)
            cur.ops.append(op)
            cur.symbols[name] = out_shapes
    if cur is not None:
        comps[cur.name] = cur
    if entry is None and comps:
        entry = list(comps)[-1]
    return comps, entry


def _dot_flops(op: Op, comp: Computation) -> float:
    out_elems = sum(_shape_elems(d) for _, d in op.out_shapes)
    m = _LHS_CDIMS_RE.search(op.line)
    if not m or not op.operands:
        return 2.0 * out_elems  # degenerate
    cdims = [int(x) for x in m.group(1).split(",") if x]
    lhs_shapes = comp.symbols.get(op.operands[0])
    if not lhs_shapes:
        return 2.0 * out_elems
    lhs_dims = ([int(x) for x in lhs_shapes[0][1].split(",") if x]
                if lhs_shapes[0][1] else [])
    k = 1
    for c in cdims:
        if c < len(lhs_dims):
            k *= lhs_dims[c]
    return 2.0 * out_elems * k


def _trip_count(cond: Computation) -> int:
    consts = []
    for op in cond.ops:
        consts += [int(x) for x in _CONST_INT_RE.findall(op.line)]
    return max(consts) if consts else 1


class HloCost:
    def __init__(self, hlo_text: str):
        self.comps, self.entry = parse_module(hlo_text)
        self._memo: dict[str, CostTotals] = {}

    def _comp_cost(self, name: str) -> CostTotals:
        if name in self._memo:
            return self._memo[name]
        comp = self.comps.get(name)
        total = CostTotals()
        self._memo[name] = total  # guards recursion
        if comp is None:
            return total
        for op in comp.ops:
            oc = op.opcode
            out_bytes = sum(_shape_bytes(d, s) for d, s in op.out_shapes)
            out_elems = sum(_shape_elems(s) for _, s in op.out_shapes)
            if oc in _FREE_OPS or not oc:
                continue
            if oc == "while":
                cm = _COND_BODY_RE.search(op.line)
                if cm:
                    cond_name, body_name = cm.groups()
                    n = _trip_count(self.comps.get(cond_name,
                                                   Computation("?")))
                    body = self._comp_cost(body_name)
                    cond = self._comp_cost(cond_name)
                    total.flops += n * (body.flops + cond.flops)
                    total.bytes += n * (body.bytes + cond.bytes)
                    for k, v in body.collective_bytes.items():
                        total.collective_bytes[k] = (
                            total.collective_bytes.get(k, 0.0) + n * v)
                        total.collective_counts[k] = (
                            total.collective_counts.get(k, 0.0)
                            + n * body.collective_counts.get(k, 0.0))
                    for k, b, m in body.collective_ops:
                        total.collective_ops.append((k, b, n * m))
                continue
            if oc in ("fusion", "call", "async-start"):
                cm = _CALLS_RE.search(op.line) or _TO_APPLY_RE.search(op.line)
                if cm:
                    inner = self._comp_cost(cm.group(1))
                    total.flops += inner.flops
                    # bytes: fusion boundary only (operands + outputs)
                    opnd_bytes = sum(
                        _shape_bytes(d, s)
                        for o in op.operands
                        for d, s in comp.symbols.get(o, []))
                    total.bytes += out_bytes + opnd_bytes
                    for k, v in inner.collective_bytes.items():
                        total.add_collective(k, v, 1.0)
                    total.collective_ops.extend(inner.collective_ops)
                continue
            if oc == "conditional":
                branches = re.findall(r"%([\w.\-]+)", op.line.split(
                    "branch_computations")[-1]) if \
                    "branch_computations" in op.line else []
                if branches:
                    costs = [self._comp_cost(b) for b in branches]
                    best = max(costs, key=lambda c: c.flops)
                    total.flops += best.flops
                    total.bytes += best.bytes
                continue
            base = oc.replace("-start", "")
            if base in COLLECTIVE_KINDS:
                total.add_collective(base, out_bytes, 1.0)
                total.collective_ops.append((base, float(out_bytes), 1.0))
                total.bytes += out_bytes
                continue
            if oc in ("dot", "convolution"):
                total.flops += _dot_flops(op, comp)
                opnd_bytes = sum(
                    _shape_bytes(d, s)
                    for o in op.operands
                    for d, s in comp.symbols.get(o, []))
                total.bytes += out_bytes + opnd_bytes
                continue
            # generic elementwise / reduce / scatter / copy / dus ...
            total.flops += out_elems
            opnd_bytes = sum(
                _shape_bytes(d, s)
                for o in op.operands
                for d, s in comp.symbols.get(o, []))
            total.bytes += out_bytes + opnd_bytes
        return total

    def totals(self) -> CostTotals:
        return self._comp_cost(self.entry)


def analyze_hlo(hlo_text: str) -> CostTotals:
    return HloCost(hlo_text).totals()
