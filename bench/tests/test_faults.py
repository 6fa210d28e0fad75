"""The comparison that decides `correct`, driven through the whole harness
on the CPU at small sizes: sound runs pass, and each fault planted under
the timed path, and the lower-precision control, turn `correct` false."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import control
import edgelist
import harness
from conftest import DIST_CELL

CELLS = ["g500-s21.pr", "g500-s21.sssp", DIST_CELL]
SSSP_CELLS = [c for c in CELLS if c.endswith(".sssp")]


def plant(monkeypatch, fault):
    """Run `fault(bound, params)` in place of each call of the program."""
    real = harness.bind_program

    def bind(repro, prog, g, mesh=None):
        bound = real(repro, prog, g, mesh)
        return lambda **params: fault(bound, params)
    monkeypatch.setattr(harness, "bind_program", bind)


def state_unchanged(bound, params):
    """The loop returns the state it started from."""
    out = dict(bound(**params))
    if "pageRank" in out:
        n = out["pageRank"].shape[0]
        out["pageRank"] = jnp.full((n,), 1.0 / n, jnp.float32)
    else:
        out["dist"] = jnp.full_like(out["dist"], 2 ** 30).at[params["src"]].set(0)
    return out


def answer_altered(bound, params):
    """One vertex's answer changed where it is produced."""
    out = dict(bound(**params))
    if "pageRank" in out:
        r = out["pageRank"]
        out["pageRank"] = r.at[jnp.argmax(r)].multiply(1.5)
    else:
        d = out["dist"]
        v = jnp.argmax(jnp.where(d < 2 ** 30, d, -1))
        out["dist"] = d.at[v].add(1)
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(drive, cell):
    line = drive(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"analytic_s", "setup_s"}
    assert line["info"]["window_compiles"] == 0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [state_unchanged, answer_altered],
                         ids=["state_unchanged", "answer_altered"])
def test_planted_fault_is_not_correct(drive, monkeypatch, cell, fault):
    plant(monkeypatch, fault)
    line = drive(cell)
    assert not line["correct"]
    assert line["failed"] == line["attempted"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_edges_left_out_is_not_correct(drive, monkeypatch, cell):
    real = harness.build_graph

    def half(repro, edges):
        keep = len(edges["src"]) // 2
        return real(repro, dict(edges, src=edges["src"][:keep], dst=edges["dst"][:keep],
                                w=edges["w"][:keep]))
    monkeypatch.setattr(harness, "build_graph", half)
    assert not drive(cell)["correct"]


@pytest.mark.parametrize("cell", SSSP_CELLS)
def test_one_edge_dropped_from_the_csr_is_not_correct(drive, monkeypatch, cell):
    """The reference reads the benchmark's edge list, not the program's
    CSR: one directed entry missing from the build shows."""
    traffic = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic", "sssp-roots.json"))
    program = harness.load_module("programs", "sssp")

    def drop_one(repro, edges):
        """The first root's lightest edge, which is its target's shortest path."""
        root = program.plan(edges, traffic, 0)["inputs"][0]["src"]
        src, dst, w = edgelist.directed(edges)
        out = np.flatnonzero(src == root)
        lightest = out[np.argmin(w[out])]
        cut = (src == root) & (dst == dst[lightest])
        return repro.graph.from_edges(edges["n"], src[~cut], dst[~cut], w[~cut])
    monkeypatch.setattr(harness, "build_graph", drop_one)
    line = drive(cell)
    assert not line["correct"], line["checks"]


def run_control(drive, tiny_root, cell, name):
    with control.control_in_place(cell, name, tiny_root):
        return drive(cell, seed=2**31 + 11)


@pytest.mark.parametrize("cell,name", [("g500-s21.pr", "bfloat16"),
                                       (DIST_CELL, "bfloat16"),
                                       ("g500-s21.sssp", "bfloat16"),
                                       ("g500-s21.sssp", "int16"),
                                       ("g500-s21.sssp", "stopped_short")])
def test_control_is_not_correct(drive, tiny_root, cell, name):
    """The reference, broken as named, in the program's place."""
    line = run_control(drive, tiny_root, cell, name)
    assert not line["correct"], line["checks"]


def test_distances_need_more_than_16_bits(drive):
    """The weights stand for Graph500's [0, 1) as 20-bit fixed point, so a
    16-bit distance cannot hold the answer and the int32 path is tested."""
    line = drive("g500-s21.sssp")
    assert line["correct"] and line["info"]["max_dist"] > 2 ** 16


def test_no_tpu_prints_no_result(capsys):
    """Off a TPU the command fails and prints no number."""
    rc = harness.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""


def test_without_the_program_it_fails(tmp_path, monkeypatch, capsys):
    """A checkout holding only BENCHMARK.json and bench/ gives no result."""
    import jax
    monkeypatch.setattr(harness, "require_accelerator", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {})
    (tmp_path / "BENCHMARK.json").write_text(
        open(os.path.join(harness.ROOT, "BENCHMARK.json")).read())
    os.symlink(os.path.join(harness.ROOT, "bench"), tmp_path / "bench")
    rc = harness.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
                      root=str(tmp_path))
    assert rc != 0 and capsys.readouterr().out == ""


def test_every_named_file_exists():
    """Each cell finds its configuration, generator, traffic, program,
    reference, loop and metric readers by name."""
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for c in bench["configs"]:
        cfg = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        harness.load_module("graphs", cfg["generator"]).generate
    for cell in bench["workloads"]:
        traffic = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                                 f"{cell['traffic']}.json"))
        prog = harness.load_module("programs", traffic["program"])
        for fn in ("plan", "output", "reference", "compare", "work_bytes"):
            assert callable(getattr(prog, fn))
        assert prog.CONTROLS
        harness.load_module("references", traffic["program"])
        harness.load_module("loops", traffic["loop"]).measure
        assert all(v is not None for v in traffic["limits"].values())
    for m in bench["per_layer"]:
        harness.load_module("metrics", m["name"]).read
    json.dumps(bench)
