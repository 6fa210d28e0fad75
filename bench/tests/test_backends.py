"""A configuration's backend, driven through the harness on the CPU: the
local cells bind on one device with no mesh, the distributed cell binds on
a mesh of exactly its own devices and gives the local cell's ranks, and the
HLO the harness hands the trace reader is that of the program the window
ran, with the exchange's collectives in it on the distributed backend."""
import re

import jax
import numpy as np
import pytest

import harness
import trace_reader
from conftest import DIST_CELL

LOCAL_CELL = "g500-s21.pr"
PARAMS = {"beta": 1e-4, "delta": 0.85, "maxIter": 100}


def spy(monkeypatch):
    """Record the program each run compiles, the mesh it binds on and the
    ranks of its last call."""
    repro = harness.import_program(harness.ROOT)
    seen = {}
    compile_bundled, bind_program = repro.core.compile_bundled, harness.bind_program

    def compile_spy(name, **kw):
        seen["prog"] = compile_bundled(name, **kw)
        return seen["prog"]

    def bind_spy(repro, prog, g, mesh=None):
        seen["mesh"] = mesh
        bound = bind_program(repro, prog, g, mesh)

        def call(**params):
            out = bound(**params)
            seen["rank"] = np.asarray(out["pageRank"])
            return out
        return call
    monkeypatch.setattr(repro.core, "compile_bundled", compile_spy)
    monkeypatch.setattr(harness, "bind_program", bind_spy)
    return seen


def test_a_configuration_without_backend_runs_local(drive, monkeypatch):
    seen = spy(monkeypatch)
    line = drive(LOCAL_CELL)
    assert line["correct"]
    assert seen["prog"].backend == "local"
    assert seen["mesh"] is None


def test_distributed_cell_binds_on_its_own_devices(drive, monkeypatch):
    seen = spy(monkeypatch)
    line = drive(DIST_CELL)
    assert line["correct"], line["checks"]
    assert line["info"]["window_compiles"] == 0
    assert line["device"]["count"] == 4
    assert seen["prog"].backend == "distributed"
    assert list(seen["mesh"].devices.flat) == jax.devices()[:4]


def test_distributed_ranks_match_the_local_cell(drive, monkeypatch, tiny_root):
    """The same seed gives the same graph; the two backends' ranks lie
    within `pr_l1_err`'s limit of each other."""
    limit = harness.load_json(f"{harness.BENCH_DIR}/traffic/pr-loop.json")["limits"]["pr_l1_err"]
    seen = spy(monkeypatch)
    seed = 2**31 + 23
    drive(LOCAL_CELL, seed=seed)
    local = seen["rank"]
    drive(DIST_CELL, seed=seed)
    assert local.shape == seen["rank"].shape
    assert np.abs(local.astype(np.float64) - seen["rank"]).sum() <= limit


def bound_programs(backend):
    repro = harness.import_program(harness.ROOT)
    from repro.graph import rmat
    g = rmat(9, 8, seed=3)
    prog = repro.core.compile_bundled("pr", backend=backend)
    mesh = harness.make_mesh(backend, jax.devices()[:4])
    harness.prepare_graph(repro, prog, g, mesh)
    return prog, g, harness.bind_program(repro, prog, g, mesh)


def test_compiled_text_is_the_local_program():
    prog, g, bound = bound_programs("local")
    want = prog.fn.lower(g, **PARAMS).compile().as_text()
    assert harness.compiled_text(bound, PARAMS) == want


def test_compiled_text_of_the_distributed_program_holds_its_exchange():
    """The shard_map runner the window calls, with the exchange's
    all-gathers, each of which the trace reader classes as collective."""
    _, _, bound = bound_programs("distributed")
    bound(**PARAMS)
    text = harness.compiled_text(bound, PARAMS)
    lines = [ln.strip() for ln in text.splitlines() if re.search(r" all-gather(-start)?\(", ln)]
    assert lines
    comps = trace_reader.computation_opcodes(text)
    for ln in lines:
        _, opcode, called = trace_reader.parse_op(ln)
        assert trace_reader.op_class(opcode, called, comps) == "collective", ln


def roofline(chips, busy_s):
    ctx = {"trace": {"busy_s": busy_s, "chips": chips}, "runs": 2,
           "work_bytes": 8.19e9, "peaks": {"hbm_bytes_per_s": 819e9}}
    return harness.load_module("metrics", "analytic_roofline").read(ctx)


def test_roofline_is_read_per_chip():
    """On one chip: the least time over busy time per run. The same work on
    four chips, each busy a quarter as long, reads the same share."""
    assert roofline(1, 0.04) == pytest.approx(100 * 0.01 / 0.02)
    assert roofline(4, 0.01) == pytest.approx(roofline(1, 0.04))
