"""Analyze post-SPMD HLO text: FLOPs, HBM-traffic proxy and collective
traffic with while-loop trip-count multiplication.

Why not ``compiled.cost_analysis()``: XLA's analysis counts each while body
ONCE — a model scanned over G layer groups under-reports by ~G (verified
empirically: a scan of 8 matmuls reports the flops of 1).  All our models
scan over layers (and chunked attention / CE scan over sequence), so the
terms must be computed from the HLO structure:

* computations are traversed from the entry; a ``while`` body/cond inherits
  ``multiplier x trip_count`` (trip count recovered from the
  ``compare(counter, constant)`` in the condition computation);
  ``call`` / ``conditional`` inherit the caller's multiplier; ``fusion``
  called computations are NOT traversed — a fusion's traffic is its
  operands + output, which models TPU fusion locality.
* FLOPs: 2 * output_elements * contraction_size per ``dot`` (operand shapes
  resolved within the computation), which captures >99% of model FLOPs.
* HBM bytes: for every materializing op, output bytes + operand bytes
  (parameters/constants/GTE/bitcast/tuple are layout ops and excluded).
* collectives: output shard bytes per op, bucketed by type.

All numbers are PER DEVICE (HLO shapes are shard shapes after SPMD).
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")


def collective_kind(op: str):
    """(base_kind, variant) for a collective op name, else (None, None).

    Async pairs lower as ``<kind>-start`` / ``<kind>-done``: the start op
    carries the payload (its output is the result — or an (input, output)
    context tuple), the done op merely retires the handle.  Counting both
    (as the old suffix-regex did) triple-counted every async collective:
    start tuple = 2x payload, done = 1x more."""
    for kind in COLLECTIVES:
        if op == kind:
            return kind, "sync"
        if op == kind + "-start":
            return kind, "start"
        if op == kind + "-done":
            return kind, "done"
    return None, None

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*((?:\([^=]*?\))|(?:\w+\[[^\]]*\]\S*))\s+([\w\-]+)\(")
_PARAM_RE = re.compile(r"%?([\w\.\-]+):\s*((?:\([^()]*\))|(?:\w+\[[^\]]*\]))")
# jax 0.4.x prints typed operands (`while((s32[], f32[...]) %tuple), ...`),
# so the operand list nests parens — anchor on the attributes instead
_WHILE_RE = re.compile(r"\bwhile\(.*condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)")
_CALL_RE = re.compile(r"(?:call|conditional)\(.*?to_apply=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_LHS_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_FUSION_KIND_RE = re.compile(r"kind=(k\w+)")
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count.*?"?n"?\s*[:=]\s*"?(\d+)')
_REDUCING_OPS_RE = re.compile(r"=\s*\S+\s+(reduce|reduce-window|scatter|sort)\(")
_LHS_BDIMS_RE = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")

_SKIP_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
    # while-carried buffer copies are elided by buffer aliasing on TPU;
    # the host backend materializes them in text — don't count.
    "copy",
    # control flow: the called computations are traversed (with trip
    # multipliers) and counted there; the wrapper op moves no bytes itself
    "call", "conditional", "while",
}


def _extract_call(line: str, op: str):
    """The operand string inside ``op( ... )`` with balanced parens (typed
    tuple operands nest parens, so a [^)]* scan truncates)."""
    i = line.find(op + "(")
    if i < 0:
        return None
    start = i + len(op) + 1
    depth = 1
    for j in range(start, len(line)):
        ch = line[j]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return line[start:j]
    return None


def _operands(opstr: str):
    """Split an operand list at top-level commas -> [(name, inline_shape)].

    Tolerates both spellings XLA has used: bare ``%name`` and the typed
    ``f32[8,16]{1,0} %name`` of jax 0.4.x (where the inline shape makes the
    local-shapes lookup unnecessary — it is returned alongside the name)."""
    parts, depth, cur = [], 0, []
    for ch in opstr:
        if ch in "([{":
            depth += 1
            cur.append(ch)
        elif ch in ")]}":
            depth -= 1
            cur.append(ch)
        elif ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    out = []
    for p in parts:
        p = p.strip()
        if not p:
            continue
        nm = re.search(r"%([\w\.\-]+)$", p)
        if nm:
            shape = p[: nm.start()].strip()
            out.append((nm.group(1), shape or None))
        else:
            out.append((p.lstrip("%"), None))
    return out


def _dims(shape_str: str):
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return None, []
    dt = m.group(1)
    dims = [int(d) for d in m.group(2).split(",") if d]
    return dt, dims


def _shape_bytes(shape_str: str) -> int:
    """Total bytes over every array shape in the string.  ``token[]`` and
    other non-array types contribute 0 (their "dtype" is not in the table);
    a tuple shape sums its elements — correct for variadic sync collectives,
    NOT for async ``-start`` tuples (use :func:`_payload_bytes` there)."""
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


def _payload_bytes(shape_str: str) -> int:
    """Payload of an async ``-start`` output: the largest array in the
    shape.  Covers ``all-reduce-start`` (plain result shape), ``all-gather-
    start`` ((input, output) tuples — output is the larger), and
    ``collective-permute-start`` ((in, out, u32[], u32[]) context tuples)."""
    best = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        best = max(best, n * _DTYPE_BYTES[dt])
    return best


@dataclass(frozen=True)
class CollectiveOp:
    """One collective instruction as seen in the HLO text (per device).

    ``bytes`` is the trip-multiplied payload; ``mult`` the while-loop
    multiplier it inherited; ``computation`` where it lives.  The plan
    auditor matches these against the plan's comm contract."""
    kind: str
    op: str
    computation: str
    shape: str
    bytes: float
    mult: float


@dataclass
class HloStats:
    flops: float = 0.0
    bytes: float = 0.0
    collectives: Dict[str, float] = field(default_factory=dict)
    # every collective instruction individually (async pairs counted once,
    # at the -start op) — the analysis subsystem audits these per-op
    collective_ops: list = field(default_factory=list)
    # opt-in (analyze_hlo(detail=True)): bytes per "computation/op[shape]"
    # key — the §Perf hillclimb uses this to find the dominant traffic.
    detail: Dict[str, float] = field(default_factory=dict)

    @property
    def collective_bytes(self) -> float:
        return float(sum(self.collectives.values()))

    def top(self, n: int = 20):
        return sorted(self.detail.items(), key=lambda kv: -kv[1])[:n]


def _split_computations(text: str) -> Dict[str, dict]:
    """name -> {header, lines} for every computation in the module."""
    comps: Dict[str, dict] = {}
    cur = None
    header_re = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*\S.*\{")
    for line in text.splitlines():
        if cur is None:
            m = header_re.match(line)
            if m:
                cur = m.group(2)
                comps[cur] = {"header": line, "lines": [], "entry": bool(m.group(1))}
                continue
        else:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur]["lines"].append(line)
    return comps


def _local_shapes(comp: dict) -> Dict[str, str]:
    """name -> shape string for params and defs in a computation."""
    shapes: Dict[str, str] = {}
    for m in _PARAM_RE.finditer(comp["header"]):
        shapes[m.group(1)] = m.group(2)
    for line in comp["lines"]:
        d = _DEF_RE.match(line)
        if d:
            shapes[d.group(1)] = d.group(2)
    return shapes


def _dot_flops(line: str, shapes: Dict[str, str], out_shape: str) -> float:
    _, out_dims = _dims(out_shape)
    opstr = _extract_call(line, "dot")
    operands = _operands(opstr) if opstr else []
    if not operands:
        return 0.0
    lhs, lhs_inline = operands[0]
    lhs_shape = lhs_inline or shapes.get(lhs)
    if lhs_shape is None:
        return 0.0
    _, lhs_dims = _dims(lhs_shape)
    cd = _LHS_CDIMS_RE.search(line)
    k = 1
    if cd:
        for i in cd.group(1).split(","):
            if i and int(i) < len(lhs_dims):
                k *= lhs_dims[int(i)]
    n_out = 1
    for d in out_dims:
        n_out *= d
    return 2.0 * n_out * k


def analyze_hlo(text: str, fallback_trip: int = 1, detail: bool = False) -> HloStats:
    comps = _split_computations(text)
    entry = next((n for n, c in comps.items() if c["entry"]), None)
    if entry is None and comps:
        entry = next(iter(comps))
    stats = HloStats(collectives=defaultdict(float))
    if entry is None:
        return stats

    visited = set()
    reducing_cache: Dict[str, bool] = {}

    def _is_reducing_fusion(line: str) -> bool:
        """A fusion whose called computation reduces (reduce/scatter/sort)
        genuinely reads its operands in full; host HLO marks these kLoop,
        so the kind= attribute alone is unreliable."""
        cm = _CALLS_RE.search(line)
        if not cm:
            return _FUSION_KIND_RE.search(line) and _FUSION_KIND_RE.search(line).group(1) == "kInput"
        called = cm.group(1)
        if called not in reducing_cache:
            body = "\n".join(comps.get(called, {"lines": []})["lines"])
            reducing_cache[called] = bool(_REDUCING_OPS_RE.search(body))
        return reducing_cache[called]

    def visit(name: str, mult: float):
        if name not in comps:
            return
        key = (name, round(mult, 6))
        if key in visited:
            return
        visited.add(key)
        comp = comps[name]
        shapes = _local_shapes(comp)
        for line in comp["lines"]:
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                tm = _TRIP_RE.search(line)  # XLA annotates known trip counts
                if tm:
                    trips = int(tm.group(1))
                else:
                    cond_text = "\n".join(comps.get(cond, {"lines": []})["lines"])
                    consts = [int(c) for c in _CONST_RE.findall(cond_text)]
                    trips = max(consts) if consts else fallback_trip
                visit(body, mult * trips)
                continue
            d = _DEF_RE.match(line)
            if not d:
                continue
            out_shape, op = d.group(2), d.group(3)
            ckind, cvariant = collective_kind(op)
            if ckind is not None and cvariant != "done":
                # sync ops may be variadic (tuple output = sum of elements);
                # async -start outputs carry (input, output) context tuples —
                # count the payload exactly once, at the start op
                b = _payload_bytes(out_shape) if cvariant == "start" else _shape_bytes(out_shape)
                stats.collectives[ckind] += mult * b
                stats.collective_ops.append(
                    CollectiveOp(kind=ckind, op=op, computation=name,
                                 shape=out_shape, bytes=mult * b, mult=mult)
                )
            if op == "dot":
                stats.flops += mult * _dot_flops(line, shapes, out_shape)
            if op not in _SKIP_BYTES_OPS and ckind is None:
                out_b = _shape_bytes(out_shape)
                operand_b = []
                opstr = _extract_call(line, op)
                if opstr:
                    for oname, inline in _operands(opstr):
                        sh = inline or shapes.get(oname)
                        if sh:
                            operand_b.append(_shape_bytes(sh))
                if op == "dynamic-slice":
                    b = out_b  # reads only the sliced region
                elif op == "dynamic-update-slice":
                    upd = operand_b[1] if len(operand_b) > 1 else 0
                    b = 2 * upd  # read update + write region (buffer is in place)
                elif op == "fusion":
                    if _is_reducing_fusion(line):
                        # reduction fusion: genuinely reads operands in full
                        b = out_b + sum(operand_b)
                    elif operand_b and max(operand_b) == out_b:
                        # output-aliased fusion.  Two shapes share this
                        # signature: a scan-buffer slice append (traffic =
                        # 2 x the small update operands) and a whole-carry
                        # in-place rewrite (traffic = read + write the
                        # buffer).  rest==0 distinguishes them.
                        rest = sum(operand_b) - max(operand_b)
                        b = 2 * rest if rest else 2 * out_b
                    else:
                        # loop fusion emits output-shaped loops: each operand
                        # contributes at most out-many element reads (slices,
                        # elementwise, broadcasts).  Counting full operands
                        # inflates every scan body by the whole xs/carry
                        # buffer per step (see EXPERIMENTS.md §Perf pair 1,
                        # iteration 2 — instrument fix).
                        b = out_b + sum(min(o, out_b) for o in operand_b)
                elif op in ("gather", "dynamic-gather"):
                    # embedding-style lookup reads out-many elements + indices
                    b = out_b + sum(min(o, out_b) for o in operand_b)
                else:
                    b = out_b + sum(operand_b)
                stats.bytes += mult * b
                if detail and b:
                    stats.detail[f"{name}/{op} {out_shape[:48]}"] = stats.detail.get(
                        f"{name}/{op} {out_shape[:48]}", 0.0
                    ) + mult * b
            cmm = _CALL_RE.search(line)
            if cmm and op in ("call", "conditional"):
                visit(cmm.group(1), mult)

    visit(entry, 1.0)
    stats.collectives = dict(stats.collectives)
    return stats


_CALLED_RE = re.compile(r"(?:calls|to_apply)=%?([\w\.\-]+)|branch_computations=\{([^}]*)\}")


def _called(line: str) -> list:
    """Computations an instruction calls (fusion, call, reduce, branches)."""
    out = []
    for m in _CALLED_RE.finditer(line):
        out += [m.group(1)] if m.group(1) else [n.strip().lstrip("%") for n in m.group(2).split(",")]
    return out


def loop_gemm_counts(text: str) -> list:
    """GEMMs (``dot`` and ``convolution`` instructions) in one iteration of
    each outermost ``while`` loop of the module, in text order: the body's
    own and those of what it calls (fusions, calls, branches), not those of
    loops nested inside it."""
    comps = _split_computations(text)

    def lines(name: str) -> list:
        return comps.get(name, {"lines": []})["lines"]

    def gemms(name: str) -> int:
        n = 0
        for line in lines(name):
            if _WHILE_RE.search(line):
                continue
            d = _DEF_RE.match(line)
            n += bool(d and d.group(3) in ("dot", "convolution"))
            n += sum(gemms(c) for c in _called(line))
        return n

    def loops(name: str) -> list:
        out = []
        for line in lines(name):
            wm = _WHILE_RE.search(line)
            out += [gemms(wm.group(2))] if wm else [c for callee in _called(line) for c in loops(callee)]
        return out

    return loops(next(n for n, c in comps.items() if c["entry"]))


def parse_collective_bytes(text: str, fallback_trip: int = 1) -> Tuple[Dict[str, float], float]:
    """Back-compat wrapper: ({type: per-device bytes}, total)."""
    s = analyze_hlo(text, fallback_trip)
    return s.collectives, s.collective_bytes
