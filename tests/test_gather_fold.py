"""One edge gather per neighbor loop, bit for bit.

The local backend evaluates a neighbor-only reduction term once per vertex
and gathers it with the loop's one neighbor-id array, and an integer
frontier relax folds the frontier into the source value (off-frontier
vertices read `rt.SENT`) before its one gather. Both are exact rewrites of
the per-edge formulas that gather every operand separately. Here every
program is compared, bit for bit, with the same program lowered through
those two-gather formulas, and the runtime's and the kernel layer's relax
copies with the two-gather relax on random distances holding INF.
"""
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Schedule, compile_bundled, compile_program
from repro.core import runtime as rt
from repro.core.api import compile_cache_clear
from repro.core.codegen.local_jax import LocalCodegen
from repro.graph import from_edges, preferential_attachment, rmat
from repro.graph.csr import INF_I32
from repro.kernels.ell_spmv import ops as kops

PARAMS = {
    "pr": dict(beta=1e-4, delta=0.85, maxIter=60),
    "sssp": dict(src=0),
    "sssp_pull": dict(src=0),
    "cc": dict(),
    "bc": dict(sourceSet=np.array([0, 7], np.int32)),
    "ppr": dict(beta=1e-4, delta=0.85, maxIter=60,
                sourceSet=np.array([0, 7, 23], np.int32)),
}

def _per_edge_term(self, expr, ctx):
    # every operand gathered per edge: nbr.p / deg(nbr) -> p[nid] / deg[nid]
    return self.ex.expr(expr, ctx)


def _per_edge_cand(self, frontier, idx, w):
    # the frontier mask and the source value gathered separately
    plus = f" + {w}" if w else ""
    return f"jnp.where({frontier}[{idx}], _d[{idx}]{plus}, rt.INF)"


def _two_gather(monkeypatch, compile_fn):
    """`compile_fn()` under the two-gather lowering, out of the compile
    cache (which is cleared on both sides, so no folded program is reused
    for it and it is reused for no folded program)."""
    with monkeypatch.context() as m:
        m.setattr(LocalCodegen, "_edge_term", _per_edge_term)
        m.setattr(LocalCodegen, "_frontier_cand", _per_edge_cand)
        compile_cache_clear()
        try:
            return compile_fn()
        finally:
            compile_cache_clear()


def _assert_bitwise(got: dict, want: dict):
    assert got.keys() == want.keys()
    for k in want:   # outputs and device counters alike
        a, b = np.asarray(got[k]), np.asarray(want[k])
        assert a.dtype == b.dtype and a.shape == b.shape, k
        assert a.tobytes() == b.tobytes(), k


@pytest.fixture(scope="module")
def graphs():
    g_rmat = rmat(9, seed=3)
    g_pl = preferential_attachment(600, m=6, seed=11)
    # directed, with sinks (out-degree 0) that have in-edges, and an
    # isolated vertex: PageRank's term divides by a zero degree there
    sinks = from_edges(8, np.array([0, 0, 1, 2, 3, 3, 5]),
                       np.array([1, 2, 2, 4, 4, 6, 4]),
                       np.array([3, 1, 7, 2, 5, 1, 9]))
    for g in (g_rmat, sinks):
        assert (np.asarray(g.out_degree) == 0).any()
    return {"rmat9": g_rmat, "powerlaw": g_pl, "sinks": sinks}


@pytest.mark.parametrize("gname", ["rmat9", "powerlaw", "sinks"])
@pytest.mark.parametrize("direction", ["auto", "push", "pull"])
@pytest.mark.parametrize("backend", ["local", "pallas"])
@pytest.mark.parametrize("name", sorted(PARAMS))
def test_bundled_program_matches_two_gather_form(monkeypatch, graphs, name,
                                                 backend, direction, gname):
    g = graphs[gname]
    sched = Schedule(direction=direction)
    prog = compile_bundled(name, backend=backend, schedule=sched)
    ref = _two_gather(monkeypatch, lambda: compile_bundled(
        name, backend=backend, schedule=sched))
    assert "rt.SENT" not in ref.source and "_nt" not in ref.source
    _assert_bitwise(prog(g, **PARAMS[name]), ref(g, **PARAMS[name]))


# a relax from caller-given distances and frontier (no re-initialization),
# so a frontier vertex can hold INF and an off-frontier one a distance
_RELAX = """
function Relax(Graph g, propNode<int> dist, propNode<bool> modified) {
    bool finished = False;
    fixedPoint until (finished : !modified) {
        forall(v in g.nodes().filter(modified == True)) {
            forall(nbr in g.neighbors(v)) {
                edge e = g.getEdge(v, nbr);
                <nbr.dist, nbr.modified> = <Min(nbr.dist, v.dist + e.weight), True>;
            }
        }
    }
}
"""


@pytest.mark.parametrize("direction", ["auto", "push", "pull"])
def test_frontier_vertex_at_inf_matches_two_gather_form(monkeypatch, graphs,
                                                        direction):
    g = graphs["rmat9"]
    n = g.num_nodes
    rng = np.random.default_rng(5)
    dist = rng.integers(0, 1000, n).astype(np.int32)
    dist[rng.random(n) < 0.4] = INF_I32
    modified = rng.random(n) < 0.5
    assert (modified & (dist == INF_I32)).any()
    sched = Schedule(direction=direction)
    prog = compile_program(_RELAX, schedule=sched)
    ref = _two_gather(monkeypatch,
                      lambda: compile_program(_RELAX, schedule=sched))
    args = dict(dist=jnp.asarray(dist), modified=jnp.asarray(modified))
    _assert_bitwise(prog(g, **args), ref(g, **args))


# --- the runtime and kernel-layer copies of the relax ------------------------

def _two_gather_relax(g, d, fr, weighted=True):
    """dist'[v] = min(dist[v], min over frontier in-neighbors u of
    dist[u] (+ w)), every operand gathered per edge; d, fr: [N] or [B, N]."""
    cand = d[..., g.edge_src] + g.weights if weighted else d[..., g.edge_src]
    cand = jnp.where(fr[..., g.edge_src], cand, rt.INF)
    return d.at[..., g.indices].min(cand)


def _random_state(g, rows=None, seed=0):
    rng = np.random.default_rng(seed)
    shape = (g.num_nodes,) if rows is None else (rows, g.num_nodes)
    d = rng.integers(0, 5000, shape).astype(np.int32)
    d[rng.random(shape) < 0.3] = INF_I32
    fr = rng.random(shape) < 0.4
    fr[..., 0] = True
    d[..., 0] = INF_I32                 # a frontier vertex at INF
    return jnp.asarray(d), jnp.asarray(fr)


@pytest.mark.parametrize("weighted", [True, False])
@pytest.mark.parametrize("direction", ["auto", "push", "pull"])
@pytest.mark.parametrize("gname", ["rmat9", "powerlaw"])
def test_relax_minplus_hybrid_matches_two_gather_form(graphs, gname,
                                                      direction, weighted):
    g = graphs[gname]
    for seed in range(3):
        d, fr = _random_state(g, seed=seed)
        got = rt.relax_minplus_hybrid(g, d, fr, threshold_frac=1 / 16,
                                      direction=direction, weighted=weighted)
        want = _two_gather_relax(g, d, fr, weighted)
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("weighted", [True, False])
@pytest.mark.parametrize("direction", ["auto", "push", "pull"])
@pytest.mark.parametrize("gname", ["rmat9", "powerlaw"])
def test_relax_minplus_hybrid_batch_matches_two_gather_form(
        graphs, gname, direction, weighted):
    g = graphs[gname]
    d, fr = _random_state(g, rows=4, seed=1)
    fr = fr.at[1].set(fr[1] & (jnp.arange(g.num_nodes) < 8))  # a push row
    got = rt.relax_minplus_hybrid_batch(g, d, fr, threshold_frac=1 / 16,
                                        direction=direction,
                                        weighted=weighted)
    want = _two_gather_relax(g, d, fr, weighted)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("rows", [None, 3])
@pytest.mark.parametrize("gname", ["rmat9", "powerlaw"])
def test_kernel_relax_push_matches_two_gather_form(graphs, gname, rows):
    g = graphs[gname]
    d, fr = _random_state(g, rows=rows, seed=2)
    got = kops._relax_push(g, d, fr)
    assert np.array_equal(np.asarray(got),
                          np.asarray(_two_gather_relax(g, d, fr)))


def test_float_relax_keeps_the_two_gather_form(graphs):
    """Floating distances have no spare maximum to fold into: the copies
    gather the mask and the value separately, with the same result."""
    g = graphs["rmat9"]
    d, fr = _random_state(g, seed=4)
    d = d.astype(jnp.float32)
    got = rt.relax_minplus_hybrid(g, d, fr, direction="push")
    assert np.array_equal(np.asarray(got),
                          np.asarray(_two_gather_relax(g, d, fr)))
    assert np.array_equal(np.asarray(kops._relax_push(g, d, fr)),
                          np.asarray(got))


# --- gathers in the generated source -------------------------------------------

_EDGE_GATHER = re.compile(
    r"\[(?::, )?g\.(?:rev_indices|edge_src|indices|rev_edge_dst)\]")


def _block(src: str, head: str) -> str:
    """The lines of the first block of `src` whose `def` matches `head`."""
    lines = src.splitlines()
    start = next(i for i, l in enumerate(lines)
                 if re.match(rf"def {head}", l.lstrip()))
    indent = len(lines[start]) - len(lines[start].lstrip())
    body = []
    for line in lines[start + 1:]:
        if line.strip() and len(line) - len(line.lstrip()) <= indent:
            break
        body.append(line)
    return "\n".join(body)


def _main(src: str) -> str:
    return src.split("\n\n\ndef ")[0]       # the program, not __refresh


@pytest.mark.parametrize("backend", ["local", "pallas"])
def test_pr_loop_body_gathers_each_edge_once(backend):
    body = _block(_main(compile_bundled("pr", backend=backend).source),
                  "_dw1_body")
    gathers = _EDGE_GATHER.findall(body)
    if backend == "local":
        assert gathers == ["[g.rev_indices]"], gathers
    else:   # the kernel sums over its own ELL view
        assert gathers == [] and "kops.gather_plustimes" in body


def test_ppr_loop_body_gathers_each_edge_once():
    body = _block(_main(compile_bundled("ppr").source), r"_bdw\d+_body")
    assert _EDGE_GATHER.findall(body) == ["[:, g.rev_indices]"]


@pytest.mark.parametrize("name", ["sssp", "sssp_pull", "cc"])
def test_relax_branches_gather_each_edge_once(name):
    src = _main(compile_bundled(name).source)
    body = _block(src, "_fp1_body")
    push, pull = _block(body, "_push"), _block(body, "_pull")
    assert _EDGE_GATHER.findall(push) == ["[g.edge_src]"]
    assert _EDGE_GATHER.findall(pull) == ["[g.rev_indices]"]
    # nothing outside the two branches gathers over the edges: the edge
    # mask the relax does not read is not emitted
    outside = body.replace(push, "").replace(pull, "")
    assert _EDGE_GATHER.findall(outside) == [], outside


@pytest.mark.parametrize("name", sorted(PARAMS))
def test_distributed_backend_keeps_its_own_gathers(name):
    """The distributed backend gathers the exchanged full buffers by global
    id, so it neither folds a term into its shard's vertex block nor
    relaxes through the local backend's sentinel."""
    src = compile_bundled(name, backend="distributed").source
    assert "_nt" not in src and "rt.SENT" not in src
