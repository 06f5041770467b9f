"""Trip-count-aware HLO cost analysis.

XLA's built-in `compiled.cost_analysis()` counts every `while` body ONCE —
with scan-over-layers (and chunked-attention / chunked-xent inner scans) that
undercounts FLOPs, bytes and collective traffic by the trip count. The
optimized HLO carries `backend_config={"known_trip_count":{"n":"N"}}` on
while ops, so we parse the module and accumulate costs recursively:

  cost(computation) = Σ_op local(op) + Σ_while trip·cost(body∪cond)
                      + Σ_fusion/call cost(called)       [flops only]

Local costs:
  * dot: 2 · prod(output dims) · prod(lhs contracting dims)
  * elementwise arithmetic: prod(output dims)
  * bytes: operands + outputs at fusion/op boundaries (fusion internals are
    on-chip and not counted — mirrors XLA's fusion-aware accounting)
  * collectives: output bytes × ring factor (all-reduce 2x, others 1x),
    multiplied through enclosing trip counts.

Shapes in an SPMD-partitioned module are PER-DEVICE, so all results are
per-chip per-step — exactly what the roofline terms need.
"""
from __future__ import annotations

import json
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "s32": 4, "s16": 2, "s8": 1,
                "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8,
                "c128": 16, "s4": 1, "u4": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\](?:\{[^}]*\})?")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_CALL_RE = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*?(\d+)')
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "power", "maximum", "minimum",
    "exponential", "log", "tanh", "rsqrt", "sqrt", "negate", "abs", "and",
    "or", "xor", "compare", "select", "convert", "floor", "ceil", "sign",
    "cosine", "sine", "logistic", "atan2", "remainder", "clamp",
    "exponential-minus-one", "log-plus-one", "cbrt", "erf",
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_COLL_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def _shapes_bytes(sig: str) -> int:
    """Sum byte sizes of all typed shapes in a string (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_elems(sig: str) -> int:
    m = _SHAPE_RE.search(sig)
    if not m:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n


class _Op:
    __slots__ = ("name", "opcode", "out_sig", "operands", "calls", "trip",
                 "line", "contracting")

    def __init__(self, name, opcode, out_sig, operands, calls, trip, line,
                 contracting):
        self.name = name
        self.opcode = opcode
        self.out_sig = out_sig
        self.operands = operands
        self.calls = calls
        self.trip = trip
        self.line = line
        self.contracting = contracting


def _parse_module(text: str) -> Tuple[Dict[str, List[_Op]], Dict[str, Dict[str, str]], Optional[str]]:
    """Returns (computations, shape tables, entry name)."""
    comps: Dict[str, List[_Op]] = {}
    shapes: Dict[str, Dict[str, str]] = {}
    entry = None
    cur = None
    for raw in text.splitlines():
        line = raw.rstrip()
        s = line.strip()
        # computation header: `%name (p: t, ...) -> t {` or `ENTRY %name ...{`
        if s.endswith("{") and ("(" in s) and ("=" not in s.split("(")[0]):
            m = re.match(r"(ENTRY\s+)?%?([\w.\-]+)\s*\(", s)
            if m:
                cur = m.group(2)
                comps[cur] = []
                shapes[cur] = {}
                if m.group(1):
                    entry = cur
                # parameter shapes from the signature
                for pm in re.finditer(r"%?([\w.\-]+):\s*((?:\([^)]*\))|(?:\w+\[[0-9,]*\](?:\{[^}]*\})?))", s):
                    shapes[cur][pm.group(1)] = pm.group(2)
            continue
        if s == "}" or s.startswith("}"):
            continue
        if cur is None:
            continue
        m = _OP_RE.match(s)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        # out signature = leading type expr
        sig_m = re.match(r"((?:\([^)]*\))|(?:\w+\[[0-9,]*\](?:\{[^}]*\})?))\s+([\w\-]+)", rest)
        if not sig_m:
            continue
        out_sig, opcode = sig_m.group(1), sig_m.group(2)
        operands = re.findall(r"%([\w.\-]+)", rest[sig_m.end():].split("),")[0]
                              if opcode != "fusion" else rest[sig_m.end():])
        # operand list: inside the first (...) after opcode
        par = rest[sig_m.end():]
        pi = par.find("(")
        ops_list = []
        if pi >= 0:
            depth = 0
            j = pi
            for j in range(pi, len(par)):
                if par[j] == "(":
                    depth += 1
                elif par[j] == ")":
                    depth -= 1
                    if depth == 0:
                        break
            ops_list = re.findall(r"%([\w.\-]+)", par[pi:j + 1])
        calls = _CALL_RE.findall(rest)
        trip_m = _TRIP_RE.search(rest)
        trip = int(trip_m.group(1)) if trip_m else None
        con_m = _CONTRACT_RE.search(rest)
        contracting = [int(x) for x in con_m.group(1).split(",") if x] \
            if con_m else []
        comps[cur].append(_Op(name, opcode, out_sig, ops_list, calls, trip,
                              s, contracting))
        shapes[cur][name] = out_sig
    return comps, shapes, entry


def _dims(sig: str) -> List[int]:
    m = _SHAPE_RE.search(sig)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


class HLOAnalysis:
    def __init__(self, text: str):
        self.comps, self.shapes, self.entry = _parse_module(text)
        self._memo: Dict[str, Dict[str, float]] = {}

    def _local_flops(self, comp: str, op: _Op) -> float:
        if op.opcode == "dot":
            out = _dims(op.out_sig)
            out_elems = 1
            for d in out:
                out_elems *= d
            k = 1
            if op.operands:
                lhs_sig = self.shapes[comp].get(op.operands[0], "")
                ld = _dims(lhs_sig)
                for c in op.contracting:
                    if c < len(ld):
                        k *= ld[c]
            return 2.0 * out_elems * k
        if op.opcode in _ELEMENTWISE:
            return float(_shape_elems(op.out_sig))
        if op.opcode in ("reduce", "reduce-window"):
            # approx: one flop per input element
            if op.operands:
                in_sig = self.shapes[comp].get(op.operands[0], op.out_sig)
                return float(_shape_elems(in_sig))
            return float(_shape_elems(op.out_sig))
        return 0.0

    def _local_bytes(self, comp: str, op: _Op) -> float:
        oc = op.opcode
        if oc in ("tuple", "get-tuple-element", "parameter", "constant",
                  "bitcast", "while", "conditional", "call", "reshape",
                  "iota", "after-all", "partition-id", "replica-id"):
            return 0.0
        out_b = _shapes_bytes(op.out_sig)
        # Sliced/gathered reads touch only the OUTPUT-sized region of the
        # operand, not the whole buffer (a scan slicing (L, d, f) stacked
        # params reads d·f per step, not L·d·f).
        if oc in ("dynamic-slice", "slice", "gather", "broadcast"):
            return float(2 * out_b)
        if oc in ("dynamic-update-slice",):
            # in-place on TPU: read+write the update region only
            upd = _shapes_bytes(self.shapes[comp].get(op.operands[1], "")) \
                if len(op.operands) > 1 else out_b
            return float(2 * upd)
        if oc in ("scatter",):
            upd = _shapes_bytes(self.shapes[comp].get(op.operands[-1], "")) \
                if op.operands else out_b
            return float(2 * upd + out_b)
        total = out_b
        for o in op.operands:
            total += _shapes_bytes(self.shapes[comp].get(o, ""))
        return float(total)

    def _fusion_bytes(self, comp: str, op: _Op) -> float:
        """Fusion boundary bytes, but an operand whose ONLY use inside the
        fused computation is a slicing op (dynamic-slice/gather/slice) is
        charged at the slice size, not the full buffer — XLA fuses scan
        param-slicing into consumers and only the slice crosses HBM."""
        callee = op.calls[0]
        body = self.comps.get(callee, [])
        shapes = self.shapes.get(callee, {})
        # parameter name -> index order as declared
        params = [o for o in body if o.opcode == "parameter"]
        # map param name -> charged bytes
        charged: Dict[str, float] = {}
        for i, pop in enumerate(params):
            full = _shapes_bytes(pop.out_sig)
            uses = [o for o in body if pop.name in o.operands]
            if uses and all(u.opcode in ("dynamic-slice", "slice", "gather")
                            and u.operands and u.operands[0] == pop.name
                            for u in uses):
                charged[pop.name] = float(
                    sum(_shapes_bytes(u.out_sig) for u in uses))
            else:
                charged[pop.name] = float(full)
        total = float(_shapes_bytes(op.out_sig))
        for i, o in enumerate(op.operands):
            if i < len(params):
                total += charged[params[i].name]
            else:
                total += _shapes_bytes(self.shapes[comp].get(o, ""))
        return total

    def cost(self, comp: Optional[str] = None) -> Dict[str, float]:
        comp = comp or self.entry
        if comp in self._memo:
            return self._memo[comp]
        res = {"flops": 0.0, "bytes": 0.0, "collective_bytes": 0.0,
               "coll": {k: 0.0 for k in _COLLECTIVES}}
        self._memo[comp] = res  # guard cycles
        for op in self.comps.get(comp, []):
            if op.opcode == "while":
                trip = op.trip if op.trip is not None else 1
                for callee in op.calls:
                    sub = self.cost(callee)
                    res["flops"] += trip * sub["flops"]
                    res["bytes"] += trip * sub["bytes"]
                    res["collective_bytes"] += trip * sub["collective_bytes"]
                    for k in _COLLECTIVES:
                        res["coll"][k] += trip * sub["coll"][k]
            elif op.opcode in ("fusion", "call", "conditional", "custom-call",
                               "reduce", "sort", "map", "scatter", "select-and-scatter"):
                # flops descend into called computations; bytes at boundary
                if op.opcode == "fusion" and op.calls:
                    res["bytes"] += self._fusion_bytes(comp, op)
                else:
                    res["bytes"] += self._local_bytes(comp, op)
                if op.opcode == "reduce":
                    res["flops"] += self._local_flops(comp, op)
                for callee in op.calls:
                    sub = self.cost(callee)
                    res["flops"] += sub["flops"]
                    res["collective_bytes"] += sub["collective_bytes"]
                    for k in _COLLECTIVES:
                        res["coll"][k] += sub["coll"][k]
            elif any(op.opcode.startswith(c) for c in _COLLECTIVES):
                base = op.opcode
                for c in _COLLECTIVES:
                    if op.opcode.startswith(c):
                        base = c
                        break
                nbytes = _shapes_bytes(op.out_sig) * _COLL_FACTOR[base]
                res["collective_bytes"] += nbytes
                res["coll"][base] += nbytes
                res["bytes"] += self._local_bytes(comp, op)
            else:
                res["flops"] += self._local_flops(comp, op)
                res["bytes"] += self._local_bytes(comp, op)
        return res

    def entry_cost(self) -> Dict[str, float]:
        out = dict(self.cost(self.entry))
        out["coll"] = dict(out["coll"])
        return out


def analyze_hlo(text: str) -> Dict[str, float]:
    return HLOAnalysis(text).entry_cost()
