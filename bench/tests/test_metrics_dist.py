"""The readers of the distributed cell's own metrics (`dist_superstep_ms`,
`collective_exposed_ms`, `shard_build_s`): on synthetic records and trace
numbers, without their input, and after the four-chip cell driven tiny
on the CPU's four devices."""
import argparse
import os
import time
import types

import numpy as np
import pytest

import harness
import program_trace

CELL = "g500-s24.dist-pr.4chip"
DIST_METRICS = ["dist_superstep_ms", "collective_exposed_ms", "shard_build_s"]


def reader(name):
    return harness.load_module("metrics", name).read


def rec(name, start, end, **extra):
    return {"name": name, "start_ns": int(start * 1e9), "end_ns": int(end * 1e9),
            "id": 0, "parent": None, **extra}


# set-up with the partition's span, then two window runs
SYNTHETIC = [
    rec("graph.dedup", 0.0, 1.0),
    rec("graph.shard", 1.5, 3.75),
    rec("run", 5.0, 6.0, counters={"_supersteps": np.int32(1), "_gather_elems": 9.0}),
    rec("run", 7.0, 8.0, counters={"_supersteps": np.int32(7), "_gather_elems": 9.0}),
    rec("run", 9.0, 10.0, counters={"_supersteps": np.int32(9), "_gather_elems": 9.0}),
]
CTX = {"runs": 2, "trace": {"busy_s": 4.0, "collective_exposed_s": 0.03}}


@pytest.fixture
def program(monkeypatch):
    """Stands in for `repro.trace` with the given records."""
    def use(records):
        fake = types.SimpleNamespace(records=lambda: list(records))
        monkeypatch.setattr(program_trace, "_trace", lambda: fake)
    return use


@pytest.mark.parametrize("name,want", [
    ("dist_superstep_ms", 1e3 * 4.0 / 16),     # busy 4 s over 7 + 9 supersteps
    ("collective_exposed_ms", 1e3 * 0.03 / 2),
    ("shard_build_s", 2.25),                   # before the window's first run
])
def test_reader_on_synthetic_records(program, name, want):
    program(SYNTHETIC)
    assert reader(name)(CTX) == pytest.approx(want)


@pytest.mark.parametrize("name", ["dist_superstep_ms", "shard_build_s"])
def test_reader_of_an_older_program_reads_none(program, name):
    """A program without the span or the distributed counter, as the
    parent of this cell is: no reading, no error."""
    program([rec("graph.dedup", 0.0, 1.0)]
            + [rec("run", t, t + 1.0, counters={"_gather_elems": 9.0})
               for t in (5.0, 7.0, 9.0)])
    assert reader(name)(CTX) is None


def test_collective_exposed_ms_without_runs_reads_none():
    assert reader("collective_exposed_ms")({"runs": 0, "trace": CTX["trace"]}) is None


def test_the_cell_lists_its_metrics():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    listed = {m["name"] for m in harness.cell_metrics(bench, CELL, "per_layer")}
    assert set(DIST_METRICS) <= listed
    assert harness.find_cell(bench, CELL)["chips"] == 4


def test_traced_cell_reports_its_metrics(drive, tiny_root, monkeypatch):
    """The four-chip cell at scale 9 through the harness with `--trace 1`,
    the trace's reduction stood in for (a CPU trace has no TPU ops): the
    program's span and counter reach the result line."""
    import jax
    import repro.trace
    import trace_reader
    monkeypatch.setattr(trace_reader, "read_dir", lambda *a: {
        "window_s": 1.0, "busy_s": 0.5, "chips": 4, "collective_exposed_s": 0.01,
        "class_s": {"gather": 0.3, "collective": 0.01},
        "control_ops": {}, "breakdown": {"device_ops": [], "idle_gaps": []}})
    repro.trace.reset()
    jax.clear_caches()
    args = argparse.Namespace(workload=CELL, seed=2**31 + 11, seconds=0.05, trace=1)
    line = harness.run_cell(args, root=tiny_root, t_start=time.perf_counter())
    assert line["correct"] and line["device"]["count"] == 4
    for name in DIST_METRICS:
        assert line["metrics"][name]["value"] > 0, name
    runs = line["info"]["runs"]
    steps = 1e3 * 0.5 / line["metrics"]["dist_superstep_ms"]["value"] / runs
    assert [round(steps)] == line["info"]["iterations"]
