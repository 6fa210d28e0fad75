"""The metric readers of what the program records about itself (its spans
and device counters, `repro.trace`): on synthetic records, without them,
and after a whole cell driven through the harness on the CPU."""
import argparse
import os
import time
import types

import numpy as np
import pytest

import harness
import program_trace

PROGRAM_METRICS = ["supersteps", "superstep_ms", "active_edge_pct", "xla_compile_s",
                   "graph_sort_s"]


def reader(name):
    return harness.load_module("metrics", name).read


def rec(name, start, end, parent=None, **extra):
    return {"name": name, "start_ns": int(start * 1e9), "end_ns": int(end * 1e9),
            "id": 0, "parent": parent, **extra}


def counters(supersteps, push, active, swept):
    return {"_supersteps": np.int32(supersteps), "_push_steps": np.int32(push),
            "_edges_active": np.float32(active), "_edges_swept": np.float32(swept)}


# a set-up (graph build, warm-up run with its compile), then two window runs
SYNTHETIC = [
    rec("graph.dedup", 0.0, 1.0),
    rec("graph.csr", 1.0, 3.0),
    rec("graph.csr", 3.0, 5.5),
    rec("graph.to_device", 5.5, 6.0),
    rec("xla_compile", 6.0, 6.5),                      # outside the program's spans
    rec("run", 7.0, 9.0, counters=counters(9, 0, 0, 0)),
    rec("xla_compile", 7.0, 8.25, parent=5),
    rec("run", 10.0, 10.1, counters=counters(10, 4, 25, 100)),
    rec("run", 11.0, 11.1, counters=counters(30, 6, 50, 300)),
    rec("xla_compile", 12.0, 13.0),                    # after the window
]
CTX = {"runs": 2, "trace": {"busy_s": 4.0}}


@pytest.fixture
def program(monkeypatch):
    """Stands in for `repro.trace` with the given records."""
    def use(records):
        fake = types.SimpleNamespace(records=lambda: list(records))
        monkeypatch.setattr(program_trace, "_trace", lambda: fake)
    return use


@pytest.mark.parametrize("name,want", [
    ("supersteps", 20.0),                  # (10 + 30) / 2 runs
    ("superstep_ms", 1e3 * 4.0 / 40),      # busy 4 s over 40 supersteps
    ("active_edge_pct", 100.0 * 75 / 400),
    ("xla_compile_s", 1.25),               # only the warm-up run's compile
    ("graph_sort_s", 5.5),                 # dedup + both CSR builds
])
def test_reader_on_synthetic_records(program, name, want):
    program(SYNTHETIC)
    assert reader(name)(CTX) == pytest.approx(want)


@pytest.mark.parametrize("name", PROGRAM_METRICS)
@pytest.mark.parametrize("records", [None, [], SYNTHETIC[:5], SYNTHETIC[:6]],
                         ids=["no_repro_trace", "no_records", "no_run", "too_few_runs"])
def test_reader_without_its_input_reads_none(monkeypatch, name, records):
    if records is None:   # a program without `repro.trace`, as before it existed
        monkeypatch.setattr(program_trace, "_trace", lambda: None)
    else:
        fake = types.SimpleNamespace(records=lambda: list(records))
        monkeypatch.setattr(program_trace, "_trace", lambda: fake)
    assert reader(name)(CTX) is None


@pytest.mark.parametrize("name", ["supersteps", "superstep_ms", "active_edge_pct"])
def test_counter_reader_without_counters_reads_none(program, name):
    """Runs whose results held no device counter (an older program)."""
    program([rec("run", 1.0, 2.0, counters={}), rec("run", 3.0, 4.0, counters={})])
    assert reader(name)(CTX) is None


def test_active_edge_pct_without_swept_edges_reads_none(program):
    """PageRank relaxes no frontier: nothing swept, no share."""
    program([rec("run", 1.0, 2.0, counters=counters(8, 0, 0, 0))] * 2)
    assert reader("active_edge_pct")(CTX) is None
    assert reader("supersteps")(CTX) == 8.0


def fresh_process():
    """The benchmark runs one cell per process: no records of the program
    and no compiled programs yet."""
    import jax
    import repro.trace
    repro.trace.reset()
    jax.clear_caches()


@pytest.mark.parametrize("cell", ["g500-s21.pr", "g500-s21.sssp"])
def test_readers_after_a_driven_cell(drive, cell):
    """A whole cell through the harness: the readers find the window's runs
    and the set-up's spans in what the program recorded (one cell per
    process, as the benchmark runs it)."""
    fresh_process()
    line = drive(cell)
    ctx = {"runs": line["info"]["runs"], "trace": {"busy_s": 1.0}}
    steps = reader("supersteps")(ctx)
    if cell.endswith(".pr"):
        assert [steps] == line["info"]["iterations"]
        assert reader("active_edge_pct")(ctx) is None
    else:
        assert steps >= 2
        assert 0 < reader("active_edge_pct")(ctx) <= 100
    assert reader("superstep_ms")(ctx) == pytest.approx(1e3 / (steps * ctx["runs"]))
    spans = line["info"]["spans_s"]
    assert 0 < reader("graph_sort_s")(ctx) <= spans["graph_build"]
    assert 0 < reader("xla_compile_s")(ctx) <= spans["compile"]


@pytest.mark.parametrize("cell", ["g500-s21.pr", "g500-s21.sssp"])
def test_traced_run_reports_the_program_metrics(drive, tiny_root, monkeypatch, cell):
    """`--trace 1` through the harness, with the trace's reduction stood in
    for (a CPU trace has no TPU ops): every metric listed for the cell
    that reads the program appears in the result line."""
    import trace_reader
    monkeypatch.setattr(trace_reader, "read_dir", lambda *a: {
        "window_s": 1.0, "busy_s": 0.5, "chips": 1, "collective_exposed_s": 0.0,
        "class_s": {"gather": 0.3, "scatter": 0.2},
        "control_ops": {}, "breakdown": {"device_ops": [], "idle_gaps": []}})
    fresh_process()
    args = argparse.Namespace(workload=cell, seed=2**31 + 7, seconds=0.05, trace=1)
    line = harness.run_cell(args, root=tiny_root, t_start=time.perf_counter())
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    listed = {m["name"] for m in harness.cell_metrics(bench, cell, "per_layer")}
    assert set(PROGRAM_METRICS) & listed <= set(line["metrics"])
    assert line["metrics"]["supersteps"]["unit"] == "count"
