"""In-program tracing: host spans, XLA compile time, device counters.

Host spans. `span(name)` times one phase of the program on the host:

    with trace.span("graph.csr"):
        ...

It opens a `jax.profiler.TraceAnnotation("repro.<name>")`, so whenever a
profiler trace is running the span sits on the trace's host plane, on the
clock of the device ops. It also appends a record (`name`, `start_ns`,
`end_ns`, `id`, `parent`: the id of the enclosing span, or None) and adds
its seconds to a per-name total. Records use the profiler's host clock,
`time.time_ns()`: a trace's `profile_start_time` plus an event's offset
lands on it (`tests/test_trace.py` checks this against a CPU trace).
Nothing is written to disk; a caller reads `seconds()` or `records()`.
Spans never run inside a jitted function and never wait for the device.

XLA compile time. One `jax.monitoring` listener, registered on import,
records every XLA compile as a record named `xla_compile` whose parent is
the span open at the time, and adds it to `seconds()["xla_compile"]`.
JAX's `backend_compile_duration` event covers a compile and a load from
the persistent compilation cache alike (the cache's own retrieval event
is nested inside it, so it is not added again).

Device counters. A generated program's result key that starts with `_` is
a device counter, not an output: the local and pallas backends return
`_supersteps`, `_push_steps`, `_edges_active` and `_edges_swept` from
every top-level loop, the distributed backend `_supersteps` and
`_gather_elems`.
`counters(result)` fetches them to the host in one transfer, after the
run. `BoundProgram.__call__` keeps a reference to each call's counters
(device scalars, not fetched) on its `run` record, so a caller can read
the counters of calls it did not hold on to.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time

import jax

# the newest records kept; a long-running service drops the oldest
MAX_RECORDS = 4096

XLA_COMPILE = "xla_compile"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_seconds: dict = {}
_next_id = 0
_open = threading.local()       # this thread's stack of open span records


def _add(name: str, start_ns: int, end_ns: int) -> dict:
    global _next_id
    stack = getattr(_open, "stack", None)
    with _lock:
        rec = {"name": name, "start_ns": start_ns, "end_ns": end_ns,
               "id": _next_id, "parent": stack[-1]["id"] if stack else None}
        _next_id += 1
        _records.append(rec)
    return rec


@contextlib.contextmanager
def span(name: str):
    """Time the enclosed host work as span `name`; yields its record."""
    rec = _add(name, time.time_ns(), 0)
    stack = _open.__dict__.setdefault("stack", [])
    stack.append(rec)
    try:
        with jax.profiler.TraceAnnotation(f"repro.{name}"):
            yield rec
    finally:
        stack.pop()
        rec["end_ns"] = time.time_ns()
        with _lock:
            _seconds[name] = _seconds.get(name, 0.0) + \
                (rec["end_ns"] - rec["start_ns"]) / 1e9


def _on_duration(event: str, duration: float, **_):
    if event != _COMPILE_EVENT:
        return
    end = time.time_ns()
    _add(XLA_COMPILE, end - int(duration * 1e9), end)
    with _lock:
        _seconds[XLA_COMPILE] = _seconds.get(XLA_COMPILE, 0.0) + duration


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def seconds() -> dict:
    """Seconds per span name (and `xla_compile`) since the last reset."""
    with _lock:
        return dict(_seconds)


def records() -> list:
    """The kept records, oldest first (spans are recorded when they open)."""
    with _lock:
        return list(_records)


def reset() -> None:
    """Forget every record and total (open spans still close normally)."""
    with _lock:
        _records.clear()
        _seconds.clear()


def device_counters(result) -> dict:
    """The device-counter entries of a result dict, still on the device."""
    return {k: v for k, v in result.items() if k.startswith("_")}


def counters(result) -> dict:
    """A result's device counters as host numbers, in one transfer."""
    return {k: v.item() for k, v in jax.device_get(device_counters(result)).items()}


def outputs(result) -> dict:
    """A result dict without its device counters."""
    return {k: v for k, v in result.items() if not k.startswith("_")}
