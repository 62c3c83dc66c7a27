"""Post-SPMD HLO text analyzer for the dry run's roofline inputs.

``compiled.cost_analysis()`` on the CPU backend neither scales while-loop
bodies by trip count nor separates collectives, so we parse the optimized
HLO text ourselves:

  * FLOPs     — from ``dot`` ops: 2 * prod(output shape) * prod(contracted
                lhs dims); scaled through the call graph (while bodies
                multiply by ``known_trip_count`` from backend_config, else
                by the constant their condition's ROOT compares the
                counter against with ``direction=LT``, as JAX's scans and
                fori_loops lower: the TPU compiler keeps no trip count).
                A loop bounded by data, whose condition compares with no
                constant (attention's KV loop over a stacked cache),
                counts as one trip: its body once per trip of the loop
                around it.
  * bytes     — HBM-traffic estimate: sum of operand + output buffer sizes
                at fusion/op boundaries (slicing ops read only the slice).
  * collective_bytes — operand sizes of all-gather / all-reduce /
                reduce-scatter / all-to-all / collective-permute, scaled by
                trip counts (the assignment's prescribed method); an async
                ``*-start`` counts as its collective, ``*-done`` not again.
  * collective_count — how many of each kind run, scaled the same way.

All numbers are PER DEVICE (post-SPMD shapes are shard shapes), which is
the form the dry run reports (``launch/dryrun.py``).
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> Tuple[int, int]:
    """returns (elements, bytes)"""
    if dtype not in _DTYPE_BYTES:
        return 0, 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n, n * _DTYPE_BYTES[dtype]


def _first_shape(text: str) -> List[Tuple[str, str]]:
    return _SHAPE_RE.findall(text)


@dataclass
class Computation:
    name: str
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: Dict[str, float] = field(default_factory=dict)
    collective_count: Dict[str, float] = field(default_factory=dict)
    # call sites: (callee_name, multiplier)
    calls: List[Tuple[str, float]] = field(default_factory=list)
    # a loop condition's bound: the constant its ROOT ``lt`` compares with
    lt_bound: Optional[float] = None
    # (name, op kind, output shapes [(dtype, dims)], rest of the line)
    instructions: List[Tuple[str, str, List[Tuple[str, str]], str]] = field(
        default_factory=list)


def _parse_instruction_shapes(line: str) -> List[Tuple[str, str]]:
    return _SHAPE_RE.findall(line)


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    # symbol table per computation: %name -> bytes / dims
    sym_bytes: Dict[str, float] = {}
    sym_dims: Dict[str, List[int]] = {}
    sym_const: Dict[str, float] = {}
    entry_name = None

    header_re = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*{")
    instr_re = re.compile(r"^\s+(ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")
    param_re = re.compile(r"%?([\w\.\-]+):\s*([\w\[\],\s\(\)]+?)(?:,|\)\s*->)")

    for raw in text.splitlines():
        m = header_re.match(raw)
        if m:
            cur = Computation(m.group(2))
            comps[cur.name] = cur
            if m.group(1):
                entry_name = cur.name
            sym_bytes = {}
            sym_dims = {}
            sym_const = {}
            # parameters from the signature
            for pm in re.finditer(r"%?([\w\.\-]+):\s*(\w+)\[([0-9,]*)\]", raw):
                _, b = _shape_bytes(pm.group(2), pm.group(3))
                sym_bytes[pm.group(1)] = b
                sym_dims[pm.group(1)] = (
                    [int(x) for x in pm.group(3).split(",")]
                    if pm.group(3) else [])
            continue
        if cur is None:
            continue
        im = instr_re.match(raw)
        if not im:
            continue
        name, rest = im.group(2), im.group(3)
        shapes = _parse_instruction_shapes(rest)
        out_bytes = 0.0
        out_dims: List[int] = []
        if shapes:
            # output shape(s): those before the op token; tuples sum
            op_split = rest.split("(", 1)[0]
            out_shapes = _SHAPE_RE.findall(op_split)
            for dt, dims in out_shapes:
                _, b = _shape_bytes(dt, dims)
                out_bytes += b
            if out_shapes:
                out_dims = ([int(x) for x in out_shapes[0][1].split(",")]
                            if out_shapes[0][1] else [])
        sym_bytes[name] = out_bytes
        sym_dims[name] = out_dims

        # op kind = first token after the '=' and output shape annotation
        opm = re.search(r"\)?\s*([a-z][a-z0-9\-]*)\(", rest)
        kind = opm.group(1) if opm else ""
        if kind.endswith("-start") and kind[:-len("-start")] in _COLLECTIVES:
            kind = kind[:-len("-start")]
        cur.instructions.append((name, kind, _SHAPE_RE.findall(
            rest[:opm.start(1)] if opm else ""), rest))

        # operand references
        args_m = re.search(r"\((.*?)\)(,|$)", rest)
        operands = []
        if args_m:
            operands = re.findall(r"%([\w\.\-]+)", args_m.group(1))

        if kind == "constant" and not out_dims:
            cm = re.search(r"constant\((-?\d+)\)", rest)
            if cm:
                sym_const[name] = float(cm.group(1))
        elif (kind == "compare" and im.group(1) and "direction=LT" in rest
              and len(operands) == 2 and operands[1] in sym_const):
            cur.lt_bound = sym_const[operands[1]]

        if kind in _COLLECTIVES:
            b = sum(sym_bytes.get(o, 0.0) for o in operands) or out_bytes
            cur.collective_bytes[kind] = cur.collective_bytes.get(kind, 0.0) + b
            cur.collective_count[kind] = cur.collective_count.get(kind, 0.0) + 1
            cur.bytes_accessed += b + out_bytes
        elif kind == "dot":
            lhs = operands[0] if operands else None
            cdims = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rest)
            contracted = 1
            if lhs is not None and cdims and lhs in sym_dims:
                for ci in cdims.group(1).split(","):
                    if ci and int(ci) < len(sym_dims[lhs]):
                        contracted *= sym_dims[lhs][int(ci)]
            out_elems = 1
            for d in out_dims:
                out_elems *= d
            cur.flops += 2.0 * out_elems * contracted
            cur.bytes_accessed += out_bytes + sum(
                sym_bytes.get(o, 0.0) for o in operands)
        elif kind == "convolution":
            out_elems = 1
            for d in out_dims:
                out_elems *= d
            rhs = operands[1] if len(operands) > 1 else None
            kelems = 1
            if rhs in sym_dims:
                for d in sym_dims[rhs][:-1]:
                    kelems *= d
            cur.flops += 2.0 * out_elems * kelems
            cur.bytes_accessed += out_bytes + sum(
                sym_bytes.get(o, 0.0) for o in operands)
        elif kind in ("parameter", "tuple", "get-tuple-element", "bitcast",
                      "constant", "after-all", "partition-id", "replica-id"):
            pass
        elif kind in ("dynamic-slice", "slice", "gather"):
            cur.bytes_accessed += 2 * out_bytes  # read slice + write out
        elif kind in ("dynamic-update-slice", "scatter"):
            upd = operands[1] if len(operands) > 1 else None
            cur.bytes_accessed += 2 * sym_bytes.get(upd, out_bytes)
        else:
            cur.bytes_accessed += out_bytes + sum(
                sym_bytes.get(o, 0.0) for o in operands)

        # call edges
        if kind == "while":
            tc = re.search(r'known_trip_count[\\"]*:\s*\{[\\"]*n[\\"]*:'
                           r'[\\"]*(\d+)', rest)
            body = re.search(r"body=%?([\w\.\-]+)", rest)
            cond = re.search(r"condition=%?([\w\.\-]+)", rest)
            if tc:
                trip = float(tc.group(1))
            else:  # callees print before their callers: cond is parsed;
                # a bound that is data counts as one trip
                c = comps.get(cond.group(1)) if cond else None
                trip = (c.lt_bound if c else None) or 1.0
            if body:
                cur.calls.append((body.group(1), trip))
            if cond:
                cur.calls.append((cond.group(1), trip + 1))
        else:
            cm = re.search(r"calls=%?([\w\.\-]+)", rest)
            if cm:
                cur.calls.append((cm.group(1), 1.0))
            for bm in re.finditer(r"branch_computations=\{([^}]*)\}", rest):
                for cname in re.findall(r"%?([\w\.\-]+)", bm.group(1)):
                    cur.calls.append((cname, 1.0))

    comps["__entry__"] = comps.get(entry_name, Computation("__missing__"))
    return comps


def run_counts(comps: Dict[str, Computation]) -> Dict[str, float]:
    """How often one run of the entry runs each computation it reaches:
    a loop body once per trip, as :func:`summarize` scales them."""
    counts = {comps["__entry__"].name: 1.0}
    # callees print before their callers: walking back from the entry,
    # every caller of a computation is done before it
    for name in reversed([n for n in comps if n != "__entry__"]):
        for callee, mult in comps[name].calls:
            counts[callee] = counts.get(callee, 0.0) + \
                counts.get(name, 0.0) * mult
    return counts


@dataclass
class HloSummary:
    flops: float
    bytes_accessed: float
    collective_bytes: Dict[str, float]
    total_collective_bytes: float
    collective_count: Dict[str, float]


def summarize(text: str) -> HloSummary:
    comps = parse_hlo(text)
    entry = comps["__entry__"]
    Totals = Tuple[float, float, Dict[str, float], Dict[str, float]]
    memo: Dict[str, Totals] = {}

    def total(name: str, depth=0) -> Totals:
        if name in memo:
            return memo[name]
        c = comps.get(name)
        if c is None or depth > 64:
            return 0.0, 0.0, {}, {}
        memo[name] = (0.0, 0.0, {}, {})  # cycle guard
        f, b = c.flops, c.bytes_accessed
        coll = dict(c.collective_bytes)
        count = dict(c.collective_count)
        for callee, mult in c.calls:
            cf, cb, cc, cn = total(callee, depth + 1)
            f += mult * cf
            b += mult * cb
            for mine, theirs in ((coll, cc), (count, cn)):
                for k, v in theirs.items():
                    mine[k] = mine.get(k, 0.0) + mult * v
        memo[name] = (f, b, coll, count)
        return memo[name]

    f, b, coll, count = total(entry.name)
    return HloSummary(flops=f, bytes_accessed=b, collective_bytes=coll,
                      total_collective_bytes=sum(coll.values()),
                      collective_count=count)
