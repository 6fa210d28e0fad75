"""Cross-backend agreement: the same DSL source must produce identical
results on local / pallas backends (distributed runs in its own process —
see test_distributed.py)."""
import numpy as np
import pytest

from repro.core import Schedule, compile_bundled
from repro.trace import outputs


@pytest.mark.parametrize("name,params", [
    ("sssp", dict(src=0)),
    ("sssp_pull", dict(src=0)),
    ("pr", dict(beta=1e-4, delta=0.85, maxIter=60)),
    ("tc", dict()),
    ("lp", dict()),
    ("kcore", dict(k=2)),
    ("ppr", dict(beta=1e-4, delta=0.85, maxIter=60,
                 sourceSet=np.array([0, 7, 23], np.int32))),
])
@pytest.mark.parametrize("gname", ["UR", "SW"])
def test_local_vs_pallas(name, params, gname, graph_suite):
    g = graph_suite[gname]
    out_l = compile_bundled(name, backend="local")(g, **params)
    out_p = compile_bundled(name, backend="pallas")(g, **params)
    for key in outputs(out_l):   # device counters differ by design
        a, b = np.asarray(out_l[key]), np.asarray(out_p[key])
        if a.dtype.kind == "f":
            np.testing.assert_allclose(a, b, atol=1e-5, err_msg=f"{name}.{key}")
        else:
            assert np.array_equal(a, b), f"{name}.{key}"


def test_bc_local_vs_pallas(graph_suite):
    g = graph_suite["UR"]
    srcs = np.array([0, 7], np.int32)
    out_l = compile_bundled("bc", backend="local")(g, sourceSet=srcs)
    out_p = compile_bundled("bc", backend="pallas")(g, sourceSet=srcs)
    np.testing.assert_allclose(np.asarray(out_l["BC"]),
                               np.asarray(out_p["BC"]), atol=1e-4)


def test_backend_sources_differ():
    l = compile_bundled("sssp", backend="local").source
    p = compile_bundled("sssp", backend="pallas").source
    assert "kops.relax_minplus" in p and "kops" not in l


# --- frontier-aware engine: power-law / edge-case coverage -------------------
# The degree-bucketed sliced-ELL layout and the push/pull direction switch
# only exercise their interesting paths on skewed graphs (multiple buckets,
# hub fallback) and degenerate frontiers; the suite graphs above are too
# uniform for that.

@pytest.fixture(scope="module")
def g_powerlaw():
    from repro.graph import preferential_attachment
    return preferential_attachment(600, m=6, seed=11)


@pytest.mark.parametrize("name,params", [
    ("sssp", dict(src=0)),
    ("sssp_pull", dict(src=0)),
    ("pr", dict(beta=1e-4, delta=0.85, maxIter=60)),
])
def test_powerlaw_local_vs_pallas(name, params, g_powerlaw):
    g = g_powerlaw
    # the generator must actually produce a bucketed view with a hub tail
    from repro.graph import to_sliced_ell
    ell = to_sliced_ell(g, reverse=True)
    assert len(ell.cols) >= 2, "power-law graph should span several buckets"
    out_l = compile_bundled(name, backend="local")(g, **params)
    out_p = compile_bundled(name, backend="pallas")(g, **params)
    for key in outputs(out_l):   # device counters differ by design
        a, b = np.asarray(out_l[key]), np.asarray(out_p[key])
        if a.dtype.kind == "f":
            np.testing.assert_allclose(a, b, atol=1e-5, err_msg=f"{name}.{key}")
        else:
            assert np.array_equal(a, b), f"{name}.{key}"


def test_powerlaw_sssp_vs_oracle(g_powerlaw):
    from repro.graph.algorithms_ref import sssp_ref
    out = compile_bundled("sssp", backend="pallas")(g_powerlaw, src=0)
    assert np.array_equal(np.asarray(out["dist"]),
                          sssp_ref(g_powerlaw, 0).astype(np.int32))


def test_empty_frontier_isolated_source():
    """Source with no out-edges: the frontier empties after one step and the
    push branch (always selected at occupancy 1) must be a clean no-op."""
    from repro.graph import from_edges
    g = from_edges(8, np.array([1, 2, 3]), np.array([2, 3, 4]),
                   np.array([5, 5, 5]))
    for backend in ["local", "pallas"]:
        out = compile_bundled("sssp", backend=backend)(g, src=7)
        dist = np.asarray(out["dist"])
        assert dist[7] == 0 and (dist[:7] >= 2**30).all(), backend
        assert bool(out["finished"])


# --- batched multi-source engine: batched vs sequential agreement ------------
# ENGINE.batch_sources turns `forall(src in sourceSet)` into chunked [B, N]
# batched passes; these pin the batched lowering to the per-source fori_loop
# (batch_sources=1) on both backends, including partial final chunks,
# power-law graphs, and disconnected components.

@pytest.fixture(scope="module")
def g_disconnected():
    from repro.graph import from_edges
    src = np.array([0, 1, 2, 8, 9, 10])
    dst = np.array([1, 2, 3, 9, 10, 11])
    return from_edges(16, src, dst, np.ones(6, np.int64), undirected=True)


@pytest.mark.parametrize("backend", ["local", "pallas"])
@pytest.mark.parametrize("gfix", ["powerlaw", "disconnected"])
def test_bc_batched_vs_sequential(backend, gfix, g_powerlaw, g_disconnected):
    g = g_powerlaw if gfix == "powerlaw" else g_disconnected
    # more sources than one chunk of the default B=4 → exercises padding too
    srcs = np.arange(0, g.num_nodes, max(g.num_nodes // 9, 1), np.int32)
    seq = compile_bundled("bc", backend=backend, batch_sources=1)
    bat = compile_bundled("bc", backend=backend, batch_sources=4)
    assert "rt.bfs_levels_batch" in bat.source and "rt.bfs_levels_batch" not in seq.source
    out_s = seq(g, sourceSet=srcs)
    out_b = bat(g, sourceSet=srcs)
    np.testing.assert_allclose(np.asarray(out_b["BC"]), np.asarray(out_s["BC"]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("gfix", ["powerlaw", "disconnected"])
def test_sssp_batched_columns_match_per_source(gfix, g_powerlaw, g_disconnected):
    """rt.sssp_multi answers B queries per sweep; every column must equal the
    single-source engine's run for that source."""
    from repro.core import runtime as rt
    g = g_powerlaw if gfix == "powerlaw" else g_disconnected
    srcs = np.arange(0, g.num_nodes, max(g.num_nodes // 7, 1), np.int32)
    dist = np.asarray(rt.sssp_multi(g, srcs))
    for i, s in enumerate(srcs):
        out = compile_bundled("sssp", backend="local")(g, src=int(s))
        assert np.array_equal(dist[i], np.asarray(out["dist"])), f"src {s}"


# --- beyond-paper programs (ppr / lp / kcore) vs their oracles ---------------
# ppr exercises the batched per-source do-while (lane scalars + frozen
# converged lanes); lp the two-sided Min relax; kcore the host-level while
# around a filtered peel.

@pytest.mark.parametrize("backend", ["local", "pallas"])
@pytest.mark.parametrize("gname", ["UR", "SW"])
def test_ppr_vs_oracle(backend, gname, graph_suite):
    from repro.graph.algorithms_ref import ppr_ref
    g = graph_suite[gname]
    srcs = np.array([0, 7, 23], np.int32)
    out = compile_bundled("ppr", backend=backend)(
        g, beta=1e-4, delta=0.85, maxIter=60, sourceSet=srcs)
    np.testing.assert_allclose(
        np.asarray(out["ppr"]), ppr_ref(g, srcs, max_iter=60),
        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", ["local", "pallas"])
def test_ppr_batched_vs_sequential(backend, graph_suite):
    """The [B, N]-lane do-while (converged lanes frozen mid-batch) must
    reproduce the per-source sequential loop exactly, partial final chunk
    included (5 sources over B=4)."""
    g = graph_suite["UR"]
    srcs = np.array([3, 11, 0, 42, 77], np.int32)
    params = dict(beta=1e-4, delta=0.85, maxIter=60, sourceSet=srcs)
    seq = compile_bundled("ppr", backend=backend, batch_sources=1)
    bat = compile_bundled("ppr", backend=backend, batch_sources=4)
    assert "while_loop" in bat.source
    np.testing.assert_allclose(np.asarray(bat(g, **params)["ppr"]),
                               np.asarray(seq(g, **params)["ppr"]),
                               rtol=1e-4, atol=1e-5)


def test_ppr_multi_rows_match_singleton_sets(graph_suite):
    """PPR is linear in the restart vector: rt.ppr_multi's row b must equal
    the compiled program's aggregate over the singleton set {sources[b]}
    (the contract the serving layer's single-query path relies on)."""
    from repro.core import runtime as rt
    g = graph_suite["SW"]
    srcs = np.array([2, 9, 31], np.int32)
    rows = np.asarray(rt.ppr_multi(g, srcs))
    prog = compile_bundled("ppr", backend="local")
    for i, s in enumerate(srcs):
        out = prog(g, beta=1e-4, delta=0.85, maxIter=100,
                   sourceSet=np.array([s], np.int32))
        np.testing.assert_allclose(rows[i], np.asarray(out["ppr"]),
                                   rtol=1e-4, atol=1e-5, err_msg=f"src {s}")


@pytest.mark.parametrize("backend", ["local", "pallas"])
def test_lp_vs_oracle(backend, g_powerlaw):
    from repro.graph.algorithms_ref import label_propagation_ref
    out = compile_bundled("lp", backend=backend)(g_powerlaw)
    assert np.array_equal(np.asarray(out["label"]),
                          label_propagation_ref(g_powerlaw))


def test_lp_under_delta_schedule(graph_suite):
    """lp's unweighted Min relax is delta-steppable (like cc): same fixed
    point under the priority schedule."""
    g = graph_suite["UR"]
    base = compile_bundled("lp", backend="local")(g)
    sched = Schedule(priority="delta", delta_bucket=8)
    out = compile_bundled("lp", backend="local", schedule=sched)(g)
    assert np.array_equal(np.asarray(out["label"]), np.asarray(base["label"]))


@pytest.mark.parametrize("backend", ["local", "pallas"])
@pytest.mark.parametrize("k", [1, 2, 3])
def test_kcore_vs_oracle(backend, k, graph_suite):
    # k=2 leaves a nontrivial survivor set on UR; k=3 cascades to empty
    # (0-out-degree vertices peel their in-neighbors); k=1 peels only sinks
    from repro.graph.algorithms_ref import kcore_ref
    g = graph_suite["UR"]
    out = compile_bundled("kcore", backend=backend)(g, k=k)
    assert np.array_equal(np.asarray(out["core"]), kcore_ref(g, k)), k


# --- delta-stepping priority schedule ----------------------------------------
# priority="delta" reorders the relaxation (bucket by bucket) but must reach
# the same fixed point as the monotonic lowering on every backend, under
# every direction policy, for any bucket width — including Δ=1 (near-Dijkstra,
# maximal bucket count) and Δ larger than any distance (degenerates to the
# monotonic sweep).

@pytest.fixture(scope="module")
def g_grid():
    from repro.graph.generators import road
    return road(24, seed=7)


@pytest.fixture(scope="module")
def grid_sssp_ref(g_grid):
    from repro.graph.algorithms_ref import sssp_ref
    return sssp_ref(g_grid, 0).astype(np.int32)


@pytest.mark.parametrize("backend", ["local", "pallas"])
@pytest.mark.parametrize("direction", ["auto", "push", "pull"])
@pytest.mark.parametrize("delta", [1, 64, 100000])
def test_sssp_delta_matches_oracle(backend, direction, delta, g_grid,
                                   grid_sssp_ref):
    sched = Schedule(priority="delta", delta_bucket=delta, direction=direction)
    out = compile_bundled("sssp", backend=backend, schedule=sched)(g_grid,
                                                                   src=0)
    assert np.array_equal(np.asarray(out["dist"]), grid_sssp_ref)


@pytest.mark.parametrize("name", ["sssp", "sssp_pull", "cc"])
def test_delta_schedule_powerlaw_agrees_with_monotonic(name, g_powerlaw):
    """Power-law graph: the hub row can push the forward-ELL view past its
    blowup cap, taking the dense relax fallback — same fixed point. cc's
    unweighted Min relax goes through the same bucketed machinery."""
    params = dict(src=0) if name.startswith("sssp") else {}
    base = compile_bundled(name, backend="local")(g_powerlaw, **params)
    sched = Schedule(priority="delta", delta_bucket=120)
    out = compile_bundled(name, backend="local", schedule=sched)(
        g_powerlaw, **params)
    for key in outputs(base):    # the counters differ by design
        assert np.array_equal(np.asarray(out[key]), np.asarray(base[key])), \
            f"{name}.{key}"


def test_bc_under_delta_schedule_rejected_at_compile_time():
    """bc has no monotone Min-relax fixedPoint, so priority="delta" is a
    static SP201 error — previously the delta lowering was silently skipped
    (batched lanes advance buckets independently); now the analysis gate
    rejects the unsound knob before any code is generated."""
    from repro.core.analysis import DiagnosticError
    sched = Schedule(priority="delta", delta_bucket=64, batch_sources=4)
    with pytest.raises(DiagnosticError) as ei:
        compile_bundled("bc", backend="local", schedule=sched)
    assert "SP201" in ei.value.codes


def test_delta_schedules_differ_in_source_only_by_knobs(g_grid):
    """Same algorithm, two bucket widths: byte-identical source except the
    baked Δ literal — the schedule-as-literal contract extends to priority."""
    a = compile_bundled("sssp", schedule=Schedule(priority="delta",
                                                  delta_bucket=41)).source
    b = compile_bundled("sssp", schedule=Schedule(priority="delta",
                                                  delta_bucket=73)).source
    assert a != b and a.replace("41", "73") == b
    mono = compile_bundled("sssp").source
    assert "_bk" in a and "_bk" not in mono


def test_single_hub_star_graph():
    """Star graph: the hub's in-row exceeds every bucket width and must be
    handled entirely by the COO hub fallback."""
    from repro.graph import ENGINE, from_edges
    n = ENGINE.min_width * ENGINE.growth ** (ENGINE.num_buckets - 1) + 64
    spokes = np.arange(1, n)
    g = from_edges(n, spokes, np.zeros(n - 1, np.int64),
                   np.ones(n - 1, np.int64), undirected=True)
    from repro.graph import to_sliced_ell
    ell = to_sliced_ell(g, reverse=True)
    assert ell.hub_rows.shape[0] == n - 1          # hub row in COO fallback
    out_l = compile_bundled("sssp", backend="local")(g, src=1)
    out_p = compile_bundled("sssp", backend="pallas")(g, src=1)
    assert np.array_equal(np.asarray(out_l["dist"]), np.asarray(out_p["dist"]))
    d = np.asarray(out_p["dist"])
    assert d[1] == 0 and d[0] == 1 and (d[2:] == 2).all()
