"""Reduces a JAX profiler trace of the measured window to device numbers.

`read_dir` loads the `.xplane.pb` the profiler wrote and keeps what the
reduction needs as plain data (`events_from_profile`): each device's op
events from its "XLA Ops" line, and the host's events, in nanoseconds on
the trace's clock. `reduce` works on that data alone, so a small recorded
trace checks it.

On a TPU each op event is named by its HLO instruction as text:
`%fusion.15 = f32[65536]{...} fusion(...), kind=kCustom, calls=%fused_computation.8`.
The reduction reads the instruction's opcode and, for a fusion, the fused
computation it calls; `op_class` looks those computations up in the
compiled module's text, so a fusion whose body holds a scatter counts as
scatter and one whose body holds a gather as gather. A collective (an
all-gather, all-reduce, reduce-scatter, all-to-all or collective-permute,
its `-start`/`-done` halves, or a fusion or async op whose computation
holds one) counts as collective, whatever else it holds.

- window: the host span `bench.window` (the benchmark's own annotation);
- busy: the union of the device's op intervals inside the window, leaving
  out the control ops (`while`, `conditional`, `call`) that only contain
  other ops; those are counted (a `conditional` per SSSP superstep);
- idle gaps: the stretches of the window with no op on the device, each
  named by the innermost host event that covers its middle;
- exposed collective time: the part of the collective ops' intervals that
  no other op on the same device covers.

Every time is a mean over the device planes read (`chips`).
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_INSTR = re.compile(r"^%?([\w.\-]+)\s*=\s*.*?\s([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMP = re.compile(r"^%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OPC = re.compile(r"=\s*[^=]*?\s([a-z][a-z0-9\-]*)\(")


def parse_op(text: str):
    """(instruction, opcode, called computation or '') of one op event."""
    m = _INSTR.match(text)
    if not m:
        return text.split(" ")[0].lstrip("%"), "unknown", ""
    calls = _CALLS.search(text)
    return m.group(1), m.group(2), calls.group(1) if calls else ""


def computation_opcodes(hlo_text: str) -> dict:
    """Computation name -> opcodes in its body, called computations
    included."""
    body, calls, cur = {}, {}, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line.strip())
        if m:
            cur = m.group(1)
            body[cur], calls[cur] = set(), set()
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        op = _OPC.search(line)
        if op:
            body[cur].add(op.group(1))
        for c in _CALLS.findall(line):
            calls[cur].add(c)

    def close(name, seen):
        ops = set(body.get(name, ()))
        for c in calls.get(name, ()):
            if c not in seen:
                ops |= close(c, seen | {c})
        return ops
    return {name: close(name, {name}) for name in body}


def is_collective(opcode: str) -> bool:
    return opcode.removesuffix("-start").removesuffix("-done") in COLLECTIVES


def op_class(opcode: str, called: str, comps: dict) -> str:
    ops = {opcode} | comps.get(called, set())
    if any(is_collective(op) for op in ops):
        return "collective"
    for cls in ("scatter", "gather"):
        if cls in ops:
            return cls
    return "other"


def events_from_profile(pd, device_ids) -> dict:
    """Plain data from a `jax.profiler.ProfileData`: per device plane,
    [instruction, opcode, called computation, start_ns, duration_ns];
    for the host, [name, start_ns, duration_ns]."""
    wanted = {f"/device:TPU:{i}" for i in device_ids}
    out = {"device": {}, "host": []}
    for plane in pd.planes:
        if plane.name in wanted:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [[*parse_op(ev.name), ev.start_ns, ev.duration_ns]
                            for ev in line.events]
            out["device"][plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[ev.name, ev.start_ns, ev.duration_ns] for ev in line.events]
    return out


def union_length(intervals) -> float:
    total, start, end = 0.0, None, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start
    return total


def gaps(intervals, lo, hi):
    """The stretches of [lo, hi] that no interval covers."""
    cur = lo
    for s, e in sorted(intervals):
        if s > cur:
            yield cur, s
        cur = max(cur, e)
    if hi > cur:
        yield cur, hi


def host_name_at(host, t) -> str:
    """The innermost host event covering time t, other than the window."""
    best = None
    for name, s, d in host:
        if name != WINDOW and s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no host event"


def reduce(events: dict, hlo_text: str = "", top: int = 10) -> dict:
    host = events["host"]
    spans = [(s, s + d) for name, s, d in host if name == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = spans[0]
    comps = computation_opcodes(hlo_text)
    busy_ns, exposed_ns, op_ns, class_ns, gap_ns, loops = 0.0, 0.0, {}, {}, {}, {}
    for ops in events["device"].values():
        leaf, compute = [], []
        for instr, opcode, called, s, d in ops:
            s, e = max(s, lo), min(s + d, hi)
            if e <= s:
                continue
            if opcode in CONTAINERS:
                loops[opcode] = loops.get(opcode, 0) + 1
                continue
            leaf.append((s, e))
            cls = op_class(opcode, called, comps)
            if cls != "collective":
                compute.append((s, e))
            key = f"{instr} ({cls})"
            op_ns[key] = op_ns.get(key, 0.0) + (e - s)
            class_ns[cls] = class_ns.get(cls, 0.0) + (e - s)
        busy = union_length(leaf)
        busy_ns += busy
        exposed_ns += busy - union_length(compute)
        for s, e in gaps(leaf, lo, hi):
            name = host_name_at(host, (s + e) / 2)
            gap_ns[name] = gap_ns.get(name, 0.0) + (e - s)
    chips = max(len(events["device"]), 1)

    def ranked(d):
        return [[k, v / chips / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / chips / 1e9, "chips": chips,
            "collective_exposed_s": exposed_ns / chips / 1e9,
            "class_s": {k: v / chips / 1e9 for k, v in class_ns.items()},
            "control_ops": {k: v / chips for k, v in loops.items()},
            "breakdown": {"device_ops": ranked(op_ns), "idle_gaps": ranked(gap_ns)}}


def read_dir(trace_dir: str, device_ids, hlo_text: str = "") -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no trace under {trace_dir}")
    events = events_from_profile(ProfileData.from_file(max(paths, key=os.path.getmtime)),
                                 device_ids)
    if not any(events["device"].values()):
        raise ValueError("the trace holds no device op")
    return reduce(events, hlo_text)
