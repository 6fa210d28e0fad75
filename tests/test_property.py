"""Property-based tests (hypothesis) on system invariants."""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed; skipping "
                    "property-based tests (the rest of the suite still runs)")
from hypothesis import given, settings, strategies as st

from repro.core import Schedule, compile_bundled
from repro.graph import from_edges
from repro.graph.csr import INF_I32, to_ell
from repro.graph.partition import blocks_1d, partition_2d


def graphs(max_n=24, max_e=80):
    @st.composite
    def _g(draw):
        n = draw(st.integers(2, max_n))
        e = draw(st.integers(1, max_e))
        src = draw(st.lists(st.integers(0, n - 1), min_size=e, max_size=e))
        dst = draw(st.lists(st.integers(0, n - 1), min_size=e, max_size=e))
        w = draw(st.lists(st.integers(1, 50), min_size=e, max_size=e))
        return from_edges(n, np.array(src), np.array(dst), np.array(w),
                          drop_self_loops=True)
    return _g()


@settings(max_examples=25, deadline=None)
@given(graphs())
def test_csr_roundtrip(g):
    """CSR → COO → CSR preserves the edge set; degrees sum to E."""
    src = np.asarray(g.edge_src)
    dst = np.asarray(g.indices)
    assert int(np.asarray(g.out_degree).sum()) == g.num_edges
    assert int(np.asarray(g.in_degree).sum()) == g.num_edges
    g2 = from_edges(g.num_nodes, src, dst, np.asarray(g.weights))
    assert np.array_equal(np.asarray(g2.indptr), np.asarray(g.indptr))
    assert np.array_equal(np.asarray(g2.indices), np.asarray(g.indices))


@settings(max_examples=20, deadline=None)
@given(graphs())
def test_partition_covers_all_edges(g):
    for p in (2, 3, 4):
        for indptr in (g.indptr, g.rev_indptr):
            b = blocks_1d(indptr, g.num_nodes, p)
            assert sum(int(b.valid(d).sum()) for d in range(p)) == g.num_edges
    part2 = partition_2d(g, 2, 2)
    assert int(part2.valid.sum()) == g.num_edges


@settings(max_examples=15, deadline=None)
@given(graphs())
def test_sssp_triangle_inequality_and_fixpoint(g):
    """dist[v] ≤ dist[u] + w(u,v) for every edge, and dist is a fixed point."""
    prog = compile_bundled("sssp")
    out = prog(g, src=0)
    dist = np.asarray(out["dist"]).astype(np.int64)
    src = np.asarray(g.edge_src)
    dst = np.asarray(g.indices)
    w = np.asarray(g.weights).astype(np.int64)
    reachable = dist[src] < INF_I32
    assert np.all(dist[dst][reachable] <= (dist[src] + w)[reachable])
    assert dist[0] == 0
    out2 = prog(g, src=0)   # idempotent
    assert np.array_equal(np.asarray(out2["dist"]), dist.astype(np.int32))


@settings(max_examples=10, deadline=None)
@given(graphs())
def test_pagerank_mass(g):
    """PR values positive; sum ≤ 1 + ε (dangling mass leaks, never grows)."""
    prog = compile_bundled("pr")
    pr = np.asarray(prog(g, beta=1e-5, delta=0.85, maxIter=100)["pageRank"])
    assert np.all(pr >= 0)
    assert pr.sum() <= 1.0 + 1e-3


@settings(max_examples=10, deadline=None)
@given(graphs(), st.randoms())
def test_tc_invariant_under_edge_permutation(g, rnd):
    """Triangle count is a graph invariant — edge insertion order must not
    matter (exercises CSR construction + dedup)."""
    prog = compile_bundled("tc")
    base = int(prog(g)["triangle_count"])
    src = np.asarray(g.edge_src)
    dst = np.asarray(g.indices)
    w = np.asarray(g.weights)
    perm = np.array(rnd.sample(range(len(src)), len(src)), np.int64)
    g2 = from_edges(g.num_nodes, src[perm], dst[perm], w[perm])
    assert int(prog(g2)["triangle_count"]) == base


@settings(max_examples=15, deadline=None)
@given(graphs())
def test_bfs_levels_valid(g):
    """Every BFS tree edge spans exactly one level; unreached stay -1."""
    from repro.core.runtime import bfs_levels
    level, depth = bfs_levels(g, 0)
    level = np.asarray(level)
    src = np.asarray(g.edge_src)
    dst = np.asarray(g.indices)
    on = (level[src] >= 0)
    assert np.all(level[dst][on] >= 0)                    # reachability closed
    assert np.all(level[dst][on] <= level[src][on] + 1)   # no level skipping
    assert level[0] == 0


def dist_schedules():
    """Valid Schedules spanning the distributed knob plane (plus the knobs
    the dist codegen shares with the other backends)."""
    return st.builds(
        Schedule,
        direction=st.sampled_from(["auto", "push", "pull"]),
        dist_frontier=st.sampled_from(["dense", "compact", "auto"]),
        dist_gather_frac=st.sampled_from([1 / 16, 0.25, 0.5, 1.0]),
        push_threshold_frac=st.sampled_from([0.0, 1 / 16, 1.0]),
        batch_sources=st.sampled_from([0, 2, 32]),
        priority=st.sampled_from(["none", "delta"]),
        delta_bucket=st.sampled_from([1, 7, 64, 500]),
    )


@settings(max_examples=10, deadline=None)
@given(graphs(max_n=16, max_e=40), dist_schedules(),
       st.sampled_from([2, 4, 8]))
def test_distributed_sssp_matches_oracle_under_any_schedule(g, sched, shards):
    """Random graph x random valid Schedule x shard count: the distributed
    result equals the NumPy oracle. Frontier-compressed and dense-gather
    supersteps exchange the same values by construction, so every point of
    the knob plane must agree exactly."""
    from repro.core import dist
    from repro.graph.algorithms_ref import sssp_ref
    prog = compile_bundled("sssp", backend="distributed", schedule=sched)
    out = prog.bind(g, mesh=dist.make_mesh_1d(shards))(src=0)
    assert np.array_equal(np.asarray(out["dist"]),
                          sssp_ref(g, 0).astype(np.int32)), sched


@settings(max_examples=6, deadline=None)
@given(graphs(max_n=14, max_e=30), dist_schedules())
def test_distributed_bc_matches_oracle_under_any_schedule(g, sched):
    """BC exercises the batched source lanes (batch_sources > 1) and the
    sequential fallback (0) over the BFS forward/reverse passes."""
    from repro.core import dist
    from repro.graph.algorithms_ref import bc_ref
    srcs = np.arange(min(3, g.num_nodes), dtype=np.int32)
    # bc has no monotone Min relax, so priority="delta" is now a
    # compile-time SP201 error (covered in test_backends_agree /
    # test_analysis); this test sweeps the remaining knob plane
    sched = sched.replace(priority="none")
    prog = compile_bundled("bc", backend="distributed", schedule=sched)
    out = prog.bind(g, mesh=dist.make_mesh_1d(4))(sourceSet=srcs)
    np.testing.assert_allclose(np.asarray(out["BC"]),
                               bc_ref(g, srcs.tolist()), atol=1e-3,
                               err_msg=repr(sched))


def _dijkstra(edges: dict, n: int, src: int) -> np.ndarray:
    """Oracle SSSP over a {(u, v): w} edge dict."""
    import heapq
    adj = {}
    for (u, v), w in edges.items():
        adj.setdefault(u, []).append((v, w))
    dist = np.full(n, int(INF_I32), np.int64)
    dist[src] = 0
    pq = [(0, src)]
    while pq:
        d, u = heapq.heappop(pq)
        if d > dist[u]:
            continue
        for v, w in adj.get(u, ()):
            nd = d + w
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(pq, (nd, v))
    return dist


@settings(max_examples=6, deadline=None)
@given(graphs(max_n=20, max_e=60), st.data())
def test_service_interleaved_updates_match_oracle(g, data):
    """Random interleavings of write batches and queries against a
    GraphService graph, under random schedules: every query answer equals
    the oracle's from-scratch replay of the edge set at that instant
    (`g.update` semantics: dels first, adds replace, last write wins)."""
    import asyncio

    from repro.serve import GraphService, ServiceConfig

    n = g.num_nodes
    sched = data.draw(st.builds(
        Schedule,
        refresh_threshold_frac=st.sampled_from([0.0, 0.25, 1.0]),
        num_buckets=st.sampled_from([1, 4]),
        batch_sources=st.sampled_from([0, 2, 32]),
    ))
    vertex = st.integers(0, n - 1)
    ops = data.draw(st.lists(st.one_of(
        st.tuples(st.just("query"), vertex),
        st.tuples(st.just("update"),
                  st.lists(st.tuples(vertex, vertex, st.integers(1, 9)),
                           max_size=4),
                  st.lists(st.tuples(vertex, vertex), max_size=4)),
    ), min_size=1, max_size=6))

    edges = {(int(u), int(v)): int(w)
             for u, v, w in zip(np.asarray(g.edge_src),
                                np.asarray(g.indices),
                                np.asarray(g.weights))}

    async def run():
        async with GraphService(ServiceConfig(max_wait_ms=0.0)) as svc:
            svc.register_graph("g", g, schedule=sched, kinds=["sssp"])
            for op in ops:
                if op[0] == "query":
                    got = np.asarray(await svc.query("g", "sssp", src=op[1]),
                                     np.int64)
                    want = _dijkstra(edges, n, op[1])
                    assert np.array_equal(got, want), (sched, op)
                else:
                    _, adds, dels = op
                    for u, v in dels:
                        edges.pop((u, v), None)
                    for u, v, w in adds:
                        edges[(u, v)] = w
                    delta = await svc.update_graph(
                        "g", adds=[(u, v) for u, v, _ in adds] or None,
                        dels=dels or None,
                        weights=[w for _, _, w in adds] or None)
                    assert delta.graph.num_edges == len(edges)

    asyncio.run(run())


@settings(max_examples=15, deadline=None)
@given(graphs())
def test_ell_view_preserves_edges(g):
    ell = to_ell(g)
    cols = np.asarray(ell.cols)
    n = g.num_nodes
    got = sorted((i, int(c)) for i in range(n) for c in cols[i] if c < n)
    want = sorted(zip(np.asarray(g.edge_src).tolist(),
                      np.asarray(g.indices).tolist()))
    assert got == want
