"""HLO-text analysis: collective ops, wire bytes, trip-count-aware totals.

``cost_analysis()`` has no collective information, so we parse the compiled
module text: every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute instruction is collected per computation, and totals are
accumulated by walking the call graph from ENTRY, multiplying through
``while`` trip counts (jax scan lowers to while with a known_trip_count
backend config).  Shapes in SPMD HLO are per-device, so operand bytes are
per-device quantities.

Wire-byte model per op (ring schedules, n = replica-group size):
  all-reduce       2 (n-1)/n x bytes(operand)
  all-gather         (n-1)/n x bytes(result)
  reduce-scatter     (n-1)/n x bytes(operand)
  all-to-all         (n-1)/n x bytes(operand)
  collective-permute           bytes(operand)

Groups whose device ids span a pod boundary (id gap >= pod_size) are
classified DCN, the rest ICI.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# ``%name = TYPE opcode(`` — TYPE is an array type or a parenthesized tuple
_DEF_RE = re.compile(r"\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*?)\s[a-z][\w\-]*\(")
# ``name: TYPE`` entries of a computation header's parameter list
_PARAM_RE = re.compile(r"([\w\.\-]+):\s*(\w+\[[\d,]*\])")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def shape_bytes(shape_str: str) -> int:
    """'bf16[256,1024]{1,0}' -> bytes.  Tuples: sum the components."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveOp:
    kind: str
    comp: str
    operand_bytes: int
    result_bytes: int
    group_size: int
    n_groups: int
    is_dcn: bool
    count: float = 1.0  # multiplied by enclosing trip counts
    is_f32: bool = False

    @property
    def wire_bytes_tpu(self) -> float:
        """XLA:CPU promotes every bf16 dot/collective to f32 (no native
        bf16); a TPU build keeps model tensors bf16 on the wire.  Halving
        f32 payloads is the documented correction (genuine-f32 payloads —
        fp32 logits etc. — are small by comparison)."""
        return self.wire_bytes / 2 if self.is_f32 else self.wire_bytes

    @property
    def wire_bytes(self) -> float:
        n = max(self.group_size, 1)
        if n == 1:
            return 0.0
        if self.kind == "all-reduce":
            return 2 * (n - 1) / n * self.operand_bytes
        if self.kind == "all-gather":
            return (n - 1) / n * self.result_bytes
        if self.kind in ("reduce-scatter", "all-to-all"):
            return (n - 1) / n * self.operand_bytes
        return float(self.operand_bytes)  # collective-permute


def _operand_bytes(args: str, types: dict[str, str]) -> int:
    """Bytes of a collective's operand list: typed operands
    (``f32[4] %x``) count directly, bare names (``%x``) through their
    definition's type."""
    typed = shape_bytes(args)
    if typed:
        return typed
    return sum(shape_bytes(types.get(a.strip().lstrip("%"), ""))
               for a in args.split(","))


def _parse_groups(attr: str, n_devices: int, pod_size: int):
    """replica_groups / source_target_pairs -> (group_size, n_groups, is_dcn)."""
    m = re.search(r"source_target_pairs=\{(\{[\d,\{\}\s]*\})\}", attr)
    if m:
        pairs = re.findall(r"\{(\d+),(\d+)\}", m.group(1) + "}")
        dcn = any(int(a) // pod_size != int(b) // pod_size for a, b in pairs)
        return 2, max(len(pairs), 1), dcn
    # iota form: replica_groups=[4,2]<=[2,2,2]T(2,1,0) or <=[8]
    m = re.search(r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](T\(([\d,]+)\))?",
                  attr)
    if m:
        out_shape = [int(x) for x in m.group(1).split(",")]
        iota_shape = [int(x) for x in m.group(2).split(",")]
        perm = ([int(x) for x in m.group(4).split(",")]
                if m.group(4) else list(range(len(iota_shape))))
        ids = np.arange(int(np.prod(iota_shape))).reshape(iota_shape)
        ids = ids.transpose(perm).reshape(out_shape)
        groups = [list(row) for row in ids]
    else:
        m = re.search(r"replica_groups=\{(.*?)\}\s*(?:,|$)", attr)
        if not m:
            return 1, 1, False
        body = m.group(1)
        groups = [[int(x) for x in g.split(",") if x.strip()]
                  for g in re.findall(r"\{([\d,\s]*)\}", "{" + body + "}")]
        if not groups:
            return 1, 1, False
    gs = max(len(g) for g in groups)
    dcn = any((max(g) // pod_size) != (min(g) // pod_size)
              for g in groups if g)
    return gs, len(groups), dcn


def parse_collectives(hlo_text: str, n_devices: int,
                      pod_size: int = 256) -> list[CollectiveOp]:
    """All collective ops with trip-count-aware counts."""
    # split into computations
    comp_re = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(([^)]*)\)\s*->.*?\{",
                         re.M)
    comps: dict[str, list[str]] = {}
    # per computation: value name -> its type text.  The compiled text
    # prints operands by name only (``all-reduce(%param.1)``), so operand
    # bytes come from the operand's definition: an instruction's result
    # type or a parameter type in the computation header.
    types: dict[str, dict[str, str]] = {}
    entry = None
    name = None
    for line in hlo_text.splitlines():
        m = comp_re.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
            types[name] = dict(_PARAM_RE.findall(m.group(2)))
            if line.startswith("ENTRY"):
                entry = name
            continue
        if name is not None:
            comps[name].append(line)
            d = _DEF_RE.match(line)
            if d:
                types[name][d.group(1)] = d.group(2)

    # per computation: collectives and calls (while bodies, calls, conds)
    ops: dict[str, list[CollectiveOp]] = {c: [] for c in comps}
    calls: dict[str, list[tuple[str, float]]] = {c: [] for c in comps}
    for cname, lines in comps.items():
        for ln in lines:
            ln = ln.strip()
            kind = None
            for k in _COLLECTIVES:
                if re.search(rf"= .*?{k}(-start)?\(", ln):
                    kind = k
                    break
            if kind is not None and "-done(" not in ln:
                res = ln.split("=", 1)
                # the result type sits AFTER the '=', before the op name:
                #   %all-gather.1 = f32[4,250]{1,0} all-gather(f32[1,250] %x)
                # (the seed parsed res[0] — the instruction name — and got 0
                # bytes for every collective result, so all-gather wire
                # bytes were silently never counted)
                head_m = re.search(rf"\s*{kind}(-start)?\(", res[1])
                # unknown print variants fall back to the whole RHS — an
                # overcount that shows up in totals, rather than a silent 0
                head = res[1][:head_m.start()] if head_m else res[1]
                shapes = [shape_bytes(m.group(0))
                          for m in _SHAPE_RE.finditer(head)]
                # async -start results are (operand, result) tuples; the
                # wire payload is the last component
                result_bytes = (shapes[-1] if "-start(" in ln
                                else sum(shapes)) if shapes else 0
                args = re.search(r"\((.*?)\)", res[1][head_m.end() - 1:]
                                 if head_m else res[1])
                operand_bytes = _operand_bytes(args.group(1), types[cname]) \
                    if args else 0
                gs, ng, dcn = _parse_groups(ln, n_devices, pod_size)
                ops[cname].append(CollectiveOp(kind, cname, operand_bytes,
                                               result_bytes, gs, ng, dcn))
                continue
            m = re.search(r"while\(.*?\).*?body=%?([\w\.\-]+)", ln)
            if m:
                tc = re.search(r'known_trip_count[\'"]?:?\s*\{[\'"]?n[\'"]?:\s*[\'"]?(\d+)', ln)
                trip = float(tc.group(1)) if tc else 1.0
                calls[cname].append((m.group(1), trip))
                cond = re.search(r"condition=%?([\w\.\-]+)", ln)
                if cond:
                    calls[cname].append((cond.group(1), trip))
                continue
            for m in re.finditer(r"(?:call|fusion)=?\(?.*?to_apply=%?([\w\.\-]+)", ln):
                calls[cname].append((m.group(1), 1.0))
            m = re.search(r"conditional\(.*?branch_computations=\{([^}]*)\}", ln)
            if m:
                for b in m.group(1).split(","):
                    calls[cname].append((b.strip().lstrip("%"), 1.0))

    # walk from entry, multiplying counts
    out: list[CollectiveOp] = []
    seen: set[tuple[str, int]] = set()

    def walk(comp: str, mult: float, depth=0):
        if comp not in comps or depth > 50:
            return
        for op in ops.get(comp, []):
            o = CollectiveOp(**{**op.__dict__})
            o.count = mult
            out.append(o)
        for callee, trip in calls.get(comp, []):
            walk(callee, mult * trip, depth + 1)

    if entry is None and comps:
        entry = next(iter(comps))
    walk(entry, 1.0)
    return out


@dataclass
class CollectiveSummary:
    total_wire_bytes: float = 0.0
    raw_wire_bytes: float = 0.0
    ici_wire_bytes: float = 0.0
    dcn_wire_bytes: float = 0.0
    by_kind: dict = field(default_factory=dict)
    n_ops: int = 0

    def to_dict(self):
        return {"total_wire_bytes": self.total_wire_bytes,
                "raw_wire_bytes": self.raw_wire_bytes,
                "ici_wire_bytes": self.ici_wire_bytes,
                "dcn_wire_bytes": self.dcn_wire_bytes,
                "by_kind": self.by_kind, "n_ops": self.n_ops}


def collective_counts(ops: list[CollectiveOp]) -> dict[str, float]:
    """Trip-count-weighted collective-instruction counts by kind.

    The overlap scheduler's invariant (see ``parallel/overlap.py``) is
    that a schedule changes only *dependency structure*: the pipelined
    graph must issue exactly the collectives the serial one does — no
    chain duplicated by a rematerialized pack, none fused away or CSE'd.
    Comparing these dicts between two compiled modules is how the HLO
    schedule test pins that down."""
    out: dict[str, float] = {}
    for op in ops:
        out[op.kind] = out.get(op.kind, 0.0) + op.count
    return out


def summarize(ops: list[CollectiveOp]) -> CollectiveSummary:
    """Totals use the TPU-dtype-corrected wire bytes; raw CPU-promoted
    bytes are kept in ``raw_wire_bytes`` for reference."""
    s = CollectiveSummary()
    for op in ops:
        wb = op.wire_bytes_tpu * op.count
        s.total_wire_bytes += wb
        s.raw_wire_bytes += op.wire_bytes * op.count
        if op.is_dcn:
            s.dcn_wire_bytes += wb
        else:
            s.ici_wire_bytes += wb
        k = s.by_kind.setdefault(op.kind, {"wire_bytes": 0.0, "count": 0.0})
        k["wire_bytes"] += wb
        k["count"] += op.count
        s.n_ops += 1
    return s
