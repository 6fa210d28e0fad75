"""Batched multi-source traversal engine + its satellite fixes.

Covers the [B, N] runtime primitives against their sequential counterparts,
the SpMM ([N+1, B] operand) form of the ELL kernel, the weakref-keyed
per-graph ELL cache of the pallas backend, and the large-graph (N² ≥ 2³¹)
edge-membership path that replaced the int32 composite key.
"""
import gc
import weakref

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compile_bundled, on_device, runtime as rt
from repro.graph import from_edges, preferential_attachment, uniform_random
from repro.graph.csr import INF_I32
from repro.kernels.ell_spmv import ops as kops
from repro.kernels.ell_spmv.kernel import ell_spmv


@pytest.fixture(scope="module")
def g_pl():
    # the runtime primitives below run eagerly on the graph's device copy
    return on_device(preferential_attachment(500, m=5, seed=7))


# --- batched runtime primitives ---------------------------------------------

def test_bfs_levels_batch_rows_match_sequential(g_pl):
    srcs = jnp.asarray(np.array([0, 3, 250, 499], np.int32))
    lv_b, _ = rt.bfs_levels_batch(g_pl, srcs)
    for i, s in enumerate(np.asarray(srcs)):
        lv, _ = rt.bfs_levels(g_pl, int(s))
        assert np.array_equal(np.asarray(lv_b)[i], np.asarray(lv)), f"row {i}"


def test_relax_hybrid_batch_rows_match_sequential(g_pl):
    g = g_pl
    srcs = np.array([0, 17, 499], np.int32)
    b, n = len(srcs), g.num_nodes
    dist = jnp.full((b, n), INF_I32, jnp.int32).at[jnp.arange(b), jnp.asarray(srcs)].set(0)
    fr = dist == 0
    for _ in range(4):   # a few steps so push AND pull rows both occur
        dist2 = rt.relax_minplus_hybrid_batch(g, dist, fr)
        for i, s in enumerate(srcs):
            d1 = rt.relax_minplus_hybrid(g, dist[i], fr[i])
            assert np.array_equal(np.asarray(dist2)[i], np.asarray(d1)), f"row {i}"
        fr = dist2 < dist
        dist = dist2


def test_sssp_multi_matches_oracle(g_pl):
    from repro.graph.algorithms_ref import sssp_ref
    srcs = np.array([0, 100, 499], np.int32)
    dist = np.asarray(rt.sssp_multi(g_pl, srcs))
    for i, s in enumerate(srcs):
        assert np.array_equal(dist[i], sssp_ref(g_pl, int(s)).astype(np.int32))


# --- SpMM kernel ([N+1, B] operand) ------------------------------------------

@pytest.mark.parametrize("semiring", ["minplus", "plustimes"])
def test_ell_spmm_columns_match_spmv(semiring):
    rng = np.random.default_rng(5)
    n, d, b = 64, 8, 5
    dt = jnp.int32 if semiring == "minplus" else jnp.float32
    cols = jnp.asarray(rng.integers(0, n + 1, size=(n, d)), jnp.int32)
    vals = jnp.asarray(rng.integers(1, 90, size=(n, d)), dt)
    x = jnp.asarray(rng.integers(0, 900, size=(n + 1, b)), dt)
    mm = ell_spmv(cols, vals, x, semiring=semiring, block_rows=32)
    assert mm.shape == (n, b)
    for j in range(b):
        mv = ell_spmv(cols, vals, x[:, j], semiring=semiring, block_rows=32)
        np.testing.assert_allclose(np.asarray(mm)[:, j], np.asarray(mv), rtol=1e-6)


def test_batched_sliced_relax_and_gather(g_pl):
    g = g_pl
    ell = kops.prepare_sliced_ell(g, reverse=True)
    srcs = np.array([0, 9, 499], np.int32)
    b, n = len(srcs), g.num_nodes
    dist = jnp.full((b, n), INF_I32, jnp.int32).at[jnp.arange(b), jnp.asarray(srcs)].set(0)
    fr = dist == 0
    for _ in range(3):
        d2 = kops.relax_minplus(ell, dist, frontier=fr, csr=g)
        for i in range(b):
            d1 = kops.relax_minplus(ell, dist[i], frontier=fr[i], csr=g)
            assert np.array_equal(np.asarray(d2)[i], np.asarray(d1)), f"row {i}"
        fr = d2 < dist
        dist = d2
    contrib = jnp.asarray(np.random.default_rng(1).random((b, n)), jnp.float32)
    gb = kops.gather_plustimes(ell, contrib)
    for i in range(b):
        np.testing.assert_allclose(np.asarray(gb)[i],
                                   np.asarray(kops.gather_plustimes(ell, contrib[i])),
                                   atol=1e-5)


@pytest.mark.parametrize("backend", ["local", "pallas"])
def test_degenerate_source_sets(backend):
    """Empty, singleton, and duplicate source sets: the chunked batched loop
    (padding lanes, zero-trip guard) must match the sequential lowering."""
    g = from_edges(40, np.arange(39), np.arange(1, 40),
                   np.ones(39, np.int64), undirected=True)
    for srcs in [np.array([], np.int32), np.array([7], np.int32),
                 np.array([3, 3, 3], np.int32)]:
        b = compile_bundled("bc", backend=backend, batch_sources=4)(g, sourceSet=srcs)
        s = compile_bundled("bc", backend=backend, batch_sources=1)(g, sourceSet=srcs)
        np.testing.assert_allclose(np.asarray(b["BC"]), np.asarray(s["BC"]),
                                   atol=1e-5, err_msg=str(srcs))


# --- per-graph GraphContext registry (weakref regression) ---------------------
# These were originally written against the pallas backend's private
# `fn._ell_cache` closure; the derived views now live in the shared
# GraphContext registry (repro.core.context), same weakref discipline.

def test_graph_context_evicts_on_gc():
    from repro.core import context
    prog = compile_bundled("sssp", backend="pallas")
    g1 = uniform_random(64, 4, seed=11)
    g2 = uniform_random(72, 4, seed=12)
    base = context.registry_size()
    prog(g1, src=0)
    prog(g2, src=0)
    assert context.contains(g1) and context.contains(g2)
    assert context.registry_size() == base + 2
    del g1, g2
    gc.collect()
    assert context.registry_size() == base, \
        "dead graphs must not pin their derived views"


def test_graph_context_survives_id_reuse():
    """A stale registry entry under a reused id must be detected (the
    weakref no longer resolves to the argument) and rebuilt, not served as
    an alias of the dead graph's views."""
    from repro.core import context
    from repro.core.context import GraphContext
    prog = compile_bundled("sssp", backend="pallas")
    g = uniform_random(64, 4, seed=13)

    class _Dead:
        pass

    stale = GraphContext(_Dead())
    context._REGISTRY[id(g)] = (weakref.ref(_Dead()), stale)
    assert not context.contains(g)
    out = prog(g, src=0)
    assert context.contains(g)
    assert context._REGISTRY[id(g)][1] is not stale
    ref = compile_bundled("sssp", backend="local")(g, src=0)
    assert np.array_equal(np.asarray(out["dist"]), np.asarray(ref["dist"]))


# --- large-graph edge membership (int32 key would overflow) -------------------

def test_edge_membership_paths_agree(g_pl):
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.integers(0, g_pl.num_nodes, 400).astype(np.int32))
    w = jnp.asarray(rng.integers(0, g_pl.num_nodes, 400).astype(np.int32))
    keyed = np.asarray(rt._is_an_edge_keyed(g_pl, u, w))
    searched = np.asarray(rt._is_an_edge_rowsearch(g_pl, u, w))
    assert np.array_equal(keyed, searched)
    assert keyed.any(), "queries should hit at least one real edge"


def test_is_an_edge_and_tc_beyond_46k_nodes():
    """N = 47000 > 46341 ⇒ N² overflows int32: the composite-key fast path is
    invalid and is_an_edge / TC must take the row-range binary search."""
    n = 47_000
    ring_src = np.arange(n, dtype=np.int64)
    ring_dst = (ring_src + 1) % n
    # five chords i→i+2 forming triangles (i, i+1, i+2), far from the wrap
    chord_i = np.array([10, 1000, 20_000, 30_000, 46_000], np.int64)
    src = np.concatenate([ring_src, chord_i])
    dst = np.concatenate([ring_dst, chord_i + 2])
    g = on_device(from_edges(n, src, dst, np.ones(len(src), np.int64),
                             undirected=True))
    assert not rt._edge_key_fits_i32(g.num_nodes)
    hits = np.asarray(rt.is_an_edge(
        g, jnp.asarray(np.array([10, 10, 46_000, 5], np.int32)),
        jnp.asarray(np.array([12, 13, 46_002, 9], np.int32))))
    assert hits.tolist() == [True, False, True, False]
    assert int(rt.wedge_count(g)) == len(chord_i)
    out = compile_bundled("tc", backend="local")(g)
    assert int(out["triangle_count"]) == len(chord_i)
