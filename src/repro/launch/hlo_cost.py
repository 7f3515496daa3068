"""Loop-aware cost analysis of optimized HLO text.

``compiled.cost_analysis()`` counts every while-loop body ONCE — under
scan-over-layers + gradient-accumulation scans that undercounts FLOPs by
~U x n_micro (measured 91x on stablelm train_4k). XLA however records
``backend_config={"known_trip_count":{"n":...}}`` on every while op, so we
re-derive the roofline numerators ourselves:

- multiplicity propagation: ENTRY has multiplicity 1; a while body/cond
  inherit caller_mult x trip_count; call/conditional/fusion callees inherit
  caller_mult. Two maps are kept: *materializing* computations (reached
  without passing through a fusion — their buffers live in HBM) and *all*
  computations (for FLOP counting inside fused dots).
- FLOPs: 2 * numel(result) * K for every dot, scaled by multiplicity.
- bytes: for materializing computations, sum (result + operand) bytes of
  every non-trivial op — an HBM-traffic proxy that treats each op as
  read-operands/write-result (fusion internals excluded, fusion boundaries
  included via the fusion op itself).
- collectives: result-shape bytes per op kind, scaled by multiplicity.

All numbers are per-device: the module analyzed is the SPMD-partitioned
per-device program.
"""
from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
                "f8e4m3": 1, "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4,
                "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->.*\{\s*$")
_LINE_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_COND_RE = re.compile(r"condition=%([\w.\-]+)")
_BODY_RE = re.compile(r"body=%([\w.\-]+)")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_TOAPPLY_RE = re.compile(r"to_apply=%([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CDIM_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _first_shape_dims(type_str: str) -> Tuple[int, ...]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return ()
    return tuple(int(d) for d in m.group(2).split(",") if d)


def _numel(type_str: str) -> int:
    n = 1
    for d in _first_shape_dims(type_str):
        n *= d
    return n


@dataclasses.dataclass
class Op:
    name: str
    type_str: str
    kind: str
    rest: str          # everything after the opening paren of the op


@dataclasses.dataclass
class Computation:
    name: str
    is_entry: bool
    ops: List[Op]
    symbols: Dict[str, str]          # op/param name -> type string


def _split_type(rest: str) -> Tuple[str, str]:
    """rest starts at the type. Returns (type_str, remainder_after_type)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return rest[:i + 1], rest[i + 1:].lstrip()
        return rest, ""
    sp = rest.find(" ")
    if sp < 0:
        return rest, ""
    return rest[:sp], rest[sp + 1:].lstrip()


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _HDR_RE.match(line.strip())
            if m:
                cur = Computation(m.group(2), bool(m.group(1)), [], {})
                # header params: "name: type, name: type"
                for pm in re.finditer(r"([\w.\-]+):\s*([^,()]+(?:\([^)]*\))?"
                                      r"(?:\[[\d,]*\])?(?:\{[\d,]*\})?)",
                                      m.group(3)):
                    cur.symbols[pm.group(1)] = pm.group(2)
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        lm = _LINE_RE.match(line)
        if not lm:
            continue
        name, rest = lm.group(1), lm.group(2)
        type_str, after = _split_type(rest)
        om = re.match(r"([\w\-]+)\(", after)
        kind = om.group(1) if om else ""
        cur.symbols[name] = type_str
        cur.ops.append(Op(name, type_str, kind, after))
    return comps


def _multiplicities(comps: Dict[str, Computation]
                    ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """(materializing_mult, flop_mult) per computation name."""
    entry = next((c.name for c in comps.values() if c.is_entry), None)
    mat: Dict[str, float] = defaultdict(float)
    flop: Dict[str, float] = defaultdict(float)
    mat[entry] = flop[entry] = 1.0
    # edges: (callee, factor, through_fusion)
    edges: Dict[str, List[Tuple[str, float, bool]]] = defaultdict(list)
    for c in comps.values():
        for op in c.ops:
            if op.kind == "while":
                trip = 1.0
                tm = _TRIP_RE.search(op.rest)
                if tm:
                    trip = float(tm.group(1))
                for rx in (_BODY_RE, _COND_RE):
                    m = rx.search(op.rest)
                    if m and m.group(1) in comps:
                        edges[c.name].append((m.group(1), trip, False))
            elif op.kind == "fusion":
                m = _CALLS_RE.search(op.rest)
                if m and m.group(1) in comps:
                    edges[c.name].append((m.group(1), 1.0, True))
            elif op.kind in ("call", "async-start"):
                m = (_TOAPPLY_RE.search(op.rest) or _CALLS_RE.search(op.rest))
                if m and m.group(1) in comps:
                    edges[c.name].append((m.group(1), 1.0, False))
            elif op.kind == "conditional":
                m = _BRANCH_RE.search(op.rest)
                if m:
                    for nm in _OPERAND_RE.finditer(m.group(1)):
                        if nm.group(1) in comps:
                            edges[c.name].append((nm.group(1), 1.0, False))
            # reduce/sort/scatter to_apply: scalar lambdas — cost ignored

    # propagate (the call graph is a DAG; iterate until fixpoint to be safe)
    for _ in range(len(comps) + 2):
        changed = False
        for src, outs in edges.items():
            for dst, fac, through_fusion in outs:
                fm = flop[src] * fac
                if fm > flop[dst]:
                    flop[dst] = fm
                    changed = True
                mm = (0.0 if through_fusion else mat[src] * fac)
                if mm > mat[dst]:
                    mat[dst] = mm
                    changed = True
        if not changed:
            break
    return dict(mat), dict(flop)


_SKIP_BYTES = {"parameter", "constant", "get-tuple-element", "tuple",
               "bitcast", "after-all", "partition-id", "replica-id", ""}


def analyze(text: str) -> Dict[str, object]:
    comps = parse_module(text)
    mat, flop = _multiplicities(comps)

    flops = 0.0
    bytes_ = 0.0
    dot_bytes = 0.0   # dot operands+results only: TPU-fusion-optimistic HBM proxy
    flash_bytes = 0.0  # the subset belonging to flash-attention score/context
                       # einsums — the Pallas flash kernel keeps these in VMEM,
                       # so a kernel-adjusted memory term subtracts them
    coll = {k: 0.0 for k in _COLLECTIVES}
    coll_count = {k: 0 for k in _COLLECTIVES}
    loops: List[Tuple[str, float]] = []

    for c in comps.values():
        fm = flop.get(c.name, 0.0)
        mm = mat.get(c.name, 0.0)
        for op in c.ops:
            if op.kind == "dot" and fm:
                k = 1
                cm = _CDIM_RE.search(op.rest)
                lhs_name = None
                args = op.rest[op.rest.find("(") + 1:]
                am = _OPERAND_RE.search(args)
                if am:
                    lhs_name = am.group(1)
                if cm and lhs_name and lhs_name in c.symbols:
                    dims = _first_shape_dims(c.symbols[lhs_name])
                    for d in cm.group(1).split(","):
                        if d and int(d) < len(dims):
                            k *= dims[int(d)]
                flops += fm * 2.0 * _numel(op.type_str) * k
                db = shape_bytes(op.type_str)
                args = op.rest[op.rest.find("(") + 1: op.rest.find(")")]
                for nm in _OPERAND_RE.finditer(args):
                    db += shape_bytes(c.symbols.get(nm.group(1), ""))
                dot_bytes += fm * db
                # flash-attention inner einsums (see models.layers._flash_fwd
                # / _fa_bwd): score and context products over the kv-chunk dim
                if ("bqkgd,bckd" in op.rest or "bkgqc,bckd" in op.rest
                        or "bkgqd,bckd" in op.rest or "bkgqc,bqkgd" in op.rest
                        or "bqkgd,bkgqc" in op.rest):
                    flash_bytes += fm * db
            if op.kind == "while" and c.name in mat:
                tm = _TRIP_RE.search(op.rest)
                loops.append((op.name, float(tm.group(1)) if tm else 1.0))
            base = op.kind.replace("-start", "")
            if base in _COLLECTIVES and mm:
                if op.kind.endswith("-done"):
                    continue
                coll[base] += mm * shape_bytes(op.type_str)
                coll_count[base] += 1
            if mm and op.kind not in _SKIP_BYTES \
                    and not op.kind.endswith("-done"):
                b = shape_bytes(op.type_str)
                args = op.rest[op.rest.find("(") + 1: op.rest.find(")")]
                for nm in _OPERAND_RE.finditer(args):
                    b += shape_bytes(c.symbols.get(nm.group(1), ""))
                bytes_ += mm * b

    return dict(flops=flops, bytes=bytes_, dot_bytes=dot_bytes,
                flash_dot_bytes=flash_bytes,
                collective_bytes={**{k: int(v) for k, v in coll.items()},
                                  "total": int(sum(coll.values())),
                                  "_counts": coll_count},
                while_loops=loops,
                n_computations=len(comps))
