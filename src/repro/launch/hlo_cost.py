"""Loop-aware HLO cost model.

XLA's `compiled.cost_analysis()` counts while/scan bodies ONCE (verified in
tests/test_roofline.py), which under-counts a scanned 94-layer stack by
~94×. This module parses the post-optimization HLO text instead and walks
the call graph (entry → while bodies ×trip-count → fusions), accumulating:

  * dot FLOPs        (2 · prod(result) · prod(contracting dims))
  * dot HBM bytes    (operands + result — matmul traffic incl. remat replays)
  * collective bytes (all-gather / all-reduce / reduce-scatter / all-to-all /
                      collective-permute output shapes)

Trip counts come from the while condition's `compare(iv, constant)` (the
canonical jax.lax.scan/fori_loop lowering; the compare may sit behind a
fusion). Unrecognized conditions (e.g. data-dependent fixed points) count
as ONE iteration and are flagged — the honest static answer.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(
    r"(pred|s4|u4|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|f8e4m3fn"
    r"|f8e5m2|c64|c128)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
_HEADER_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\((.*)\)\s*->\s*.+\{\s*$")
_PARAM_RE = re.compile(r"%?([\w.\-]+)\s*:\s*(\(?[^,()]+(?:\([^)]*\))?\)?)")
_WHILE_RE = re.compile(
    r"\bwhile\(.*?\)\s*,\s*condition=%?([\w.\-]+)\s*,\s*body=%?([\w.\-]+)")
_CALLS_RE = re.compile(r"\b(?:calls|to_apply|branch_computations=\{)[=]?%?([\w.\-]+)")
_CONST_RE = re.compile(r"s32\[\]\s*constant\((\d+)\)")
_DIR_RE = re.compile(r"direction=(LT|LE|GT|GE|NE)")
_COLLECTIVE = ("all-gather(", "all-reduce(", "reduce-scatter(", "all-to-all(",
               "collective-permute(", "all-gather-start(", "all-reduce-start(",
               "collective-permute-start(")


def _bytes_of(shape_txt: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_txt):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _dims_of(shape_txt: str):
    m = _SHAPE_RE.search(shape_txt)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",")] if m.group(2) else []


@dataclass
class Comp:
    name: str
    lines: list = field(default_factory=list)
    symbols: dict = field(default_factory=dict)   # var name -> result type text


def _split(txt: str):
    comps = {}
    cur = None
    for line in txt.splitlines():
        h = _HEADER_RE.match(line)
        if h and ("->" in line):
            cur = Comp(name=h.group(1))
            comps[cur.name] = cur
            for pm in _PARAM_RE.finditer(h.group(2)):
                cur.symbols[pm.group(1)] = pm.group(2)
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        cur.lines.append(line)
        im = _INSTR_RE.match(line)
        if im:
            name, rhs = im.groups()
            # result type = leading shape text of rhs (may be a tuple)
            cur.symbols[name] = rhs.split(" ")[0] if rhs else ""
            # parameters defined inline: "%p = f32[..] parameter(0)"
    return comps


def _operand_names(rhs: str):
    """Operand variable names of the top-level op in an instruction rhs."""
    op = rhs.find("(")
    if op < 0:
        return []
    depth = 0
    end = op
    for i in range(op, len(rhs)):
        if rhs[i] == "(":
            depth += 1
        elif rhs[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    inner = rhs[op + 1:end]
    return re.findall(r"%([\w.\-]+)", inner)


@dataclass
class CompCost:
    flops: float = 0.0
    dot_bytes: float = 0.0
    coll_bytes: float = 0.0
    whiles: list = field(default_factory=list)    # (cond, body)
    calls: list = field(default_factory=list)     # names


def _analyze_comp(comp: Comp) -> CompCost:
    c = CompCost()
    for line in comp.lines:
        im = _INSTR_RE.match(line)
        if not im:
            wm = _WHILE_RE.search(line)
            if wm:
                c.whiles.append(wm.groups())
            continue
        _, rhs = im.groups()
        head = rhs.split("metadata")[0]
        wm = _WHILE_RE.search(head)
        if wm:
            c.whiles.append(wm.groups())
            continue
        if " dot(" in head or head.startswith("dot("):
            result_type = head.split(" ")[0]
            ops = _operand_names(head[head.find("dot("):])
            lhs_type = comp.symbols.get(ops[0], "") if ops else ""
            lhs_dims = _dims_of(lhs_type)
            contract = 1
            cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", head)
            if cm and cm.group(1) and lhs_dims:
                for ci in cm.group(1).split(","):
                    if int(ci) < len(lhs_dims):
                        contract *= lhs_dims[int(ci)]
            relems = 1
            for d in _dims_of(result_type):
                relems *= d
            c.flops += 2.0 * relems * contract
            rhs_type = comp.symbols.get(ops[1], "") if len(ops) > 1 else ""
            c.dot_bytes += (_bytes_of(result_type) + _bytes_of(lhs_type)
                            + _bytes_of(rhs_type))
            continue
        if any(k in head for k in _COLLECTIVE):
            c.coll_bytes += _bytes_of(head.split(" ")[0])
        for cn in _CALLS_RE.findall(head):
            c.calls.append(cn)
    return c


def _trip_count(comps, costs, cond_name):
    comp = comps.get(cond_name)
    if comp is None:
        return None
    consts = []
    for line in comp.lines:
        consts += [int(x) for x in _CONST_RE.findall(line)]
    texts = [l for l in comp.lines]
    for cn in costs[cond_name].calls:
        if cn in comps:
            texts += comps[cn].lines
    direction = None
    for l in texts:
        dm = _DIR_RE.search(l)
        if dm:
            direction = dm.group(1)
            break
    if direction in ("LT", "NE") and consts:
        return max(consts)
    if direction == "LE" and consts:
        return max(consts) + 1
    return None


def collective_breakdown(hlo_text: str, top: int = 12):
    """Per-(op, shape) collective bytes with loop multipliers — the §Perf
    profiling view ('which all-gather is eating the step')."""
    comps = _split(hlo_text)
    costs = {name: _analyze_comp(c) for name, c in comps.items()}
    detail = {}

    def visit(name, mult, depth=0):
        if name not in comps or depth > 64:
            return
        comp = comps[name]
        for line in comp.lines:
            im = _INSTR_RE.match(line)
            if not im:
                continue
            head = im.group(2).split("metadata")[0]
            for kind in _COLLECTIVE:
                if kind in head:
                    shape = head.split(" ")[0]
                    key = (kind.rstrip("("), shape)
                    b = _bytes_of(shape) * mult
                    cnt, tot = detail.get(key, (0, 0.0))
                    detail[key] = (cnt + mult, tot + b)
                    break
        c = costs[name]
        for cond, body in c.whiles:
            trips = _trip_count(comps, costs, cond) or 1
            visit(body, mult * trips, depth + 1)
        for cn in c.calls:
            visit(cn, mult, depth + 1)

    entry = None
    for name in comps:
        if name.startswith("main"):
            entry = name
    visit(entry, 1)
    rows = sorted(((tot, cnt, kind, shape)
                   for (kind, shape), (cnt, tot) in detail.items()),
                  reverse=True)
    return rows[:top]


def analyze(hlo_text: str) -> dict:
    comps = _split(hlo_text)
    costs = {name: _analyze_comp(c) for name, c in comps.items()}
    unknown = []

    memo = {}

    def total(name, depth=0):
        if name in memo:
            return memo[name]
        if name not in costs or depth > 64:
            return (0.0, 0.0, 0.0)
        c = costs[name]
        f, db, cb = c.flops, c.dot_bytes, c.coll_bytes
        for cond, body in c.whiles:
            trips = _trip_count(comps, costs, cond)
            if trips is None:
                trips = 1
                unknown.append(body)
            bf, bdb, bcb = total(body, depth + 1)
            cf, cdb, ccb = total(cond, depth + 1)
            f += trips * (bf + cf)
            db += trips * (bdb + cdb)
            cb += trips * (bcb + ccb)
        for cn in c.calls:
            bf, bdb, bcb = total(cn, depth + 1)
            f += bf
            db += bdb
            cb += bcb
        memo[name] = (f, db, cb)
        return memo[name]

    entry = None
    for name in comps:
        if name.startswith("main"):
            entry = name
    if entry is None and comps:
        entry = list(comps)[-1]
    f, db, cb = total(entry)
    return {"flops": f, "dot_bytes": db, "collective_bytes": cb,
            "entry": entry, "unknown_trip_bodies": sorted(set(unknown)),
            "num_computations": len(comps)}
