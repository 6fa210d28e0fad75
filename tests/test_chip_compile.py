"""Compiles of the main path for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached. These tests compile the per-iteration kernels of
the local backend, and its whole generated `sssp` and `pr` programs, at
the shapes of an RMAT scale-21 graph (the size
`chip_smoke.py` runs) and check they fit one v5e's 16 GB and that each
neighbor loop gathers over the edges once; they compile the distributed
`pr` at the four-chip benchmark cell's shapes for a v5e 2x2; and they pin
what Mosaic answers for the Pallas ELL kernel. Nothing runs, so they say
nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so under several pytest
workers only the worker given this file may try, and every worker must
collect the same tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import compile_bundled, runtime as rt
from repro.graph.csr import CSRGraph
from repro.kernels.ell_spmv.kernel import ell_spmv
from repro.kernels.ell_spmv.ops import MOSAIC_ERRORS
from repro.kernels.tc_matmul.kernel import tc_matmul

HBM_BYTES = 16 * 10 ** 9          # one TPU v5e
N = 2 ** 21                       # rmat(21, edge_factor=16)
E = 32_417_925                    # its edges after dedup (seed 0)
LANES = 32                        # Schedule.batch_sources: one serve sweep
PR_LANES = 8                      # the smoke's concurrent ppr users
ELL_WIDTH, ELL_ROWS, ELL_BLOCK = 128, 4096, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _graph(sharding) -> CSRGraph:
    v = lambda n: _shape(sharding, (n,))
    return CSRGraph(
        indptr=v(N + 1), indices=v(E), weights=v(E), edge_src=v(E),
        rev_indptr=v(N + 1), rev_indices=v(E), rev_weights=v(E),
        rev_edge_dst=v(E), out_degree=v(N), in_degree=v(N), edge_key=v(E),
        num_nodes=N, num_edges=E, max_out_degree=1 << 17,
        max_in_degree=1 << 17)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_batched_relax_fits_one_chip(one_chip):
    """One superstep of a 32-lane SSSP sweep (what a coalesced serve sweep
    runs), push/pull switch included."""
    step = jax.jit(lambda g, d, f: rt.relax_minplus_hybrid_batch(
        g, d, f, threshold_frac=1 / 16))
    compiled = step.lower(_graph(one_chip), _shape(one_chip, (LANES, N)),
                          _shape(one_chip, (LANES, N), jnp.bool_)).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_pagerank_reverse_edge_sum_fits_one_chip(one_chip):
    """PR's pull: a lane-batched segment sum over the reverse edges."""
    pull = jax.jit(lambda vals, g: rt.segment_sum_batch(
        vals, g.rev_edge_dst, N))
    compiled = pull.lower(_shape(one_chip, (PR_LANES, E), jnp.float32),
                          _graph(one_chip)).compile()
    assert _device_bytes(compiled) < HBM_BYTES


PROGRAMS = [
    ("sssp", dict(src=jnp.int32), ("fp1.body", "fp1.push", "fp1.pull")),
    ("pr", dict(beta=jnp.float32, delta=jnp.float32, maxIter=jnp.int32),
     ("dw1.body",)),
]


def _compile_program(sharding, name, params):
    args = {k: _shape(sharding, (), dt) for k, dt in params.items()}
    return compile_bundled(name).fn.lower(_graph(sharding), **args).compile()


@pytest.mark.parametrize("name,params,scopes", PROGRAMS)
def test_generated_program_fits_one_chip(one_chip, name, params, scopes):
    """The whole generated program, superstep counters included: it fits
    one v5e, its HLO names the loop and relax scopes, and it holds no host
    callback."""
    compiled = _compile_program(one_chip, name, params)
    text = compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES
    assert all(s in text for s in scopes), scopes
    assert "callback" not in text.lower()


def _computations(hlo: str) -> dict:
    """Computation name -> its instruction lines, from HLO text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(", line)
        if head and line.rstrip().endswith("{"):
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None:
            cur.append(line)
    return comps


def _reached(comps: dict, root: str) -> set:
    """`root` and every computation it calls, however deep."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo.extend(n for line in comps[c]
                        for n in re.findall(r"%([\w.\-]+)", line)
                        if n in comps)
    return seen


def _edge_gathers(comps: dict, root: str) -> int:
    """E-sized gathers in `root` and what it calls."""
    gather = re.compile(rf"= \w+\[{E}\]\S* gather\(")
    return sum(bool(gather.search(line)) for c in _reached(comps, root)
               for line in comps[c])


def _callee(lines, op: str, attr: str) -> list:
    """The computations `attr` names on the `op` instructions of `lines`."""
    return [n for line in lines if f" {op}(" in line
            for n in re.findall(r"%([\w.\-]+)",
                                re.search(rf"{attr}=(\{{[^}}]*\}}|\S+)",
                                          line).group(1))]


def test_one_edge_gather_per_sweep(one_chip):
    """Each neighbor loop gathers one vertex array over the edges: the
    PageRank loop body divides by the degree before its gather, and each
    branch of the SSSP relax folds the frontier into the distances before
    its gather."""
    hlo = {name: _compile_program(one_chip, name, params).as_text()
           for name, params, _ in PROGRAMS}
    pr = _computations(hlo["pr"])
    (body,) = [b for c in pr.values() for b in _callee(c, "while", "body")]
    assert _edge_gathers(pr, body) == 1

    sssp = _computations(hlo["sssp"])
    (body,) = [b for c in sssp.values()
               for b in _callee(c, "while", "body")]
    branches = _callee(sssp[body], "conditional", "branch_computations")
    assert len(branches) == 2
    assert [_edge_gathers(sssp, b) for b in branches] == [1, 1]


# the four-chip cell: Graph500 scale 24 split 1-D over a v5e 2x2, each
# chip's out- and in-edges padded to the longest chip's (about E/4 of the
# 0.5G directed edges, with room for the blocks' imbalance)
DIST_N = 2 ** 24
DIST_EMAX = 132_000_000


def test_distributed_pr_at_scale_24_fits_four_chips(topo):
    """The distributed `pr` program at the four-chip cell's shapes: every
    chip's shards (which it holds whether the program reads them or not)
    and the program's temporaries fit one v5e, and the ranks cross the
    chips by all-gather."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import dist, runtime_dist as rtd
    mesh = dist.make_mesh((4,), (rtd.AXIS,), devices=topo.devices)
    rows = NamedSharding(mesh, P(rtd.AXIS, None))
    block = DIST_N // 4
    gd = {k: jax.ShapeDtypeStruct((4, DIST_EMAX), jnp.int32, sharding=rows)
          for k in ("esrc", "edst", "ew", "esrc_local", "idst", "isrc", "iw",
                    "idst_local")}
    gd.update({k: jax.ShapeDtypeStruct((4, DIST_EMAX), jnp.bool_, sharding=rows)
               for k in ("evalid", "ivalid")})
    gd["own_ids"] = jax.ShapeDtypeStruct((4, block), jnp.int32, sharding=rows)
    rep = NamedSharding(mesh, P())
    gd.update({k: jax.ShapeDtypeStruct((DIST_N,), jnp.int32, sharding=rep)
               for k in ("out_degree_rep", "in_degree_rep")})
    gd["n_true_rep"] = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    prog = compile_bundled("pr", backend="distributed")
    names = ("beta", "delta", "maxIter")
    runner = dist._runner(prog, gd, mesh, names, prog.dist_meta)
    compiled = runner.lower(gd, 1e-4, 0.85, 100).compile()
    shards = sum(a.size * a.dtype.itemsize // 4 for k, a in gd.items()
                 if not k.endswith("_rep"))
    m = compiled.memory_analysis()
    assert shards + m.temp_size_in_bytes + m.output_size_in_bytes < HBM_BYTES
    assert "all-gather" in compiled.as_text()


@pytest.mark.parametrize("lanes,error", [(None, MOSAIC_ERRORS[0]),
                                         (LANES, MOSAIC_ERRORS[1])])
def test_mosaic_refuses_ell_spmv(one_chip, lanes, error):
    """The refusal `compile_program(backend="pallas")` quotes on a TPU is
    still what Mosaic says; if this starts to compile, the pallas backend
    can be let onto the chip."""
    x_shape = (N + 1,) if lanes is None else (N + 1, lanes)
    cols = _shape(one_chip, (ELL_ROWS, ELL_WIDTH))
    spmv = jax.jit(lambda c, v, x: ell_spmv(
        c, v, x, semiring="minplus", block_rows=ELL_BLOCK, interpret=False))
    with pytest.raises(Exception, match=error):
        spmv.lower(cols, cols, _shape(one_chip, x_shape)).compile()


def test_tc_matmul_compiles(one_chip):
    lower = _shape(one_chip, (1024, 1024), jnp.float32)
    compiled = jax.jit(lambda a: tc_matmul(a, block=128, interpret=False)
                       ).lower(lower).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_backend_refuses_on_tpu(monkeypatch):
    """On a TPU the pallas backend raises instead of running the jnp
    fallback under its name; elsewhere it compiles."""
    assert compile_bundled("sssp", backend="pallas").backend == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="Only 2D gather"):
        compile_bundled("sssp", backend="pallas")
    assert compile_bundled("sssp", backend="local").backend == "local"

