"""Per-kernel shape/dtype sweeps: pallas_call (interpret=True) vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import preferential_attachment
from repro.graph.csr import INF_I32
from repro.kernels.ell_spmv.kernel import ell_spmv
from repro.kernels.ell_spmv.ops import (gather_plustimes, prepare_ell,
                                        prepare_sliced_ell, relax_minplus)
from repro.kernels.ell_spmv.ref import ell_spmv_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ops import gqa_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.tc_matmul.kernel import tc_matmul
from repro.kernels.tc_matmul.ops import count_triangles_dense, prepare_lower
from repro.kernels.tc_matmul.ref import tc_matmul_ref


# --- ell_spmv ---------------------------------------------------------------

@pytest.mark.parametrize("n,d,block", [(64, 8, 32), (128, 16, 64), (96, 24, 32)])
@pytest.mark.parametrize("semiring", ["minplus", "plustimes"])
def test_ell_spmv_sweep(n, d, block, semiring):
    rng = np.random.default_rng(n + d)
    dt = jnp.int32 if semiring == "minplus" else jnp.float32
    cols = jnp.asarray(rng.integers(0, n + 1, size=(n, d)), jnp.int32)
    if semiring == "minplus":
        vals = jnp.asarray(rng.integers(1, 100, size=(n, d)), dt)
        x = jnp.asarray(rng.integers(0, 1000, size=(n + 1,)), dt)
    else:
        vals = jnp.asarray(rng.random((n, d)), dt)
        x = jnp.asarray(rng.random((n + 1,)), dt)
    got = ell_spmv(cols, vals, x, semiring=semiring, block_rows=block)
    ref = ell_spmv_ref(cols, vals, x, semiring=semiring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


def test_relax_matches_bellman_ford_step(g_medium):
    g = g_medium
    cols, wts, block = prepare_ell(g, reverse=True)
    dist = jnp.full((g.num_nodes,), INF_I32, jnp.int32).at[0].set(0)
    # one kernel sweep == one full Bellman-Ford relaxation round
    got = relax_minplus(cols, wts, dist, block_rows=block)
    ref = np.asarray(dist).copy()
    src = np.asarray(g.edge_src)
    dst = np.asarray(g.indices)
    w = np.asarray(g.weights)
    cand = np.where(ref[src] < INF_I32, ref[src] + w, INF_I32)
    np.minimum.at(ref, dst, cand)
    assert np.array_equal(np.asarray(got), ref)


def test_gather_matches_segment_sum(g_social):
    g = g_social
    cols, _, block = prepare_ell(g, reverse=True)
    contrib = jnp.asarray(np.random.default_rng(0).random(g.num_nodes), jnp.float32)
    got = gather_plustimes(cols, contrib, block_rows=block)[: g.num_nodes]
    ref = jax.ops.segment_sum(contrib[g.rev_indices], g.rev_edge_dst,
                              num_segments=g.num_nodes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)


# --- sliced-ELL (degree-bucketed engine layout) ------------------------------

@pytest.fixture(scope="module")
def g_skewed():
    return preferential_attachment(400, m=5, seed=3)


def test_sliced_relax_matches_dense(g_skewed):
    g = g_skewed
    cols, wts, block = prepare_ell(g, reverse=True)
    ell = prepare_sliced_ell(g, reverse=True)
    dist = jnp.full((g.num_nodes,), INF_I32, jnp.int32).at[0].set(0)
    for _ in range(3):   # a few sweeps so non-trivial values propagate
        dense = relax_minplus(cols, wts, dist, block_rows=block)
        sliced = relax_minplus(ell, dist)
        assert np.array_equal(np.asarray(sliced), np.asarray(dense))
        dist = dense


def test_sliced_relax_frontier_push_pull_agree(g_skewed):
    """Forcing push and pull must give bit-identical relaxations."""
    g = g_skewed
    ell = prepare_sliced_ell(g, reverse=True)
    dist = jnp.full((g.num_nodes,), INF_I32, jnp.int32).at[0].set(0)
    for _ in range(4):
        frontier = dist < INF_I32
        push = relax_minplus(ell, dist, frontier=frontier, csr=g,
                             threshold_frac=1.0)    # always push
        pull = relax_minplus(ell, dist, frontier=frontier, csr=g,
                             threshold_frac=0.0)    # always pull
        assert np.array_equal(np.asarray(push), np.asarray(pull))
        dist = push


def test_sliced_bucket_kernel_path(g_skewed, monkeypatch):
    """Force the Pallas-kernel branch of the bucket ops (interpret mode on
    CPU) — off-TPU runs otherwise only exercise the pure-jnp fallback, which
    would leave the real kernel dispatch (block sizing, x blockspec of
    length n+1) untested until first TPU contact."""
    from repro.kernels.ell_spmv import ops as kops
    monkeypatch.setattr(kops, "_use_kernel", lambda: True)
    g = g_skewed
    ell = prepare_sliced_ell(g, reverse=True)
    dist = jnp.full((g.num_nodes,), INF_I32, jnp.int32).at[0].set(0)
    cols, wts, block = prepare_ell(g, reverse=True)
    for _ in range(2):
        dense = relax_minplus(cols, wts, dist, block_rows=block)
        sliced = relax_minplus(ell, dist)
        assert np.array_equal(np.asarray(sliced), np.asarray(dense))
        dist = dense
    contrib = jnp.asarray(np.random.default_rng(2).random(g.num_nodes), jnp.float32)
    got = gather_plustimes(ell, contrib)
    ref = jax.ops.segment_sum(contrib[g.rev_indices], g.rev_edge_dst,
                              num_segments=g.num_nodes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)


def test_sliced_gather_matches_segment_sum(g_skewed):
    g = g_skewed
    ell = prepare_sliced_ell(g, reverse=True)
    contrib = jnp.asarray(np.random.default_rng(1).random(g.num_nodes), jnp.float32)
    got = gather_plustimes(ell, contrib)
    ref = jax.ops.segment_sum(contrib[g.rev_indices], g.rev_edge_dst,
                              num_segments=g.num_nodes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)


def test_pad_nodes_rebuilds_edge_key():
    """The cached edge_key encodes num_nodes; pad_nodes must rebuild it or
    is_an_edge silently misses real edges on padded graphs."""
    from repro.core.runtime import is_an_edge
    from repro.graph import from_edges, pad_nodes
    g = from_edges(10, np.array([1, 2, 3]), np.array([2, 3, 4]))
    gp = pad_nodes(g, 8)
    assert gp.num_nodes == 16
    u = jnp.asarray([1, 2, 3, 4])
    w = jnp.asarray([2, 3, 4, 5])
    expect = np.array([True, True, True, False])
    assert np.array_equal(np.asarray(is_an_edge(g, u, w)), expect)
    assert np.array_equal(np.asarray(is_an_edge(gp, u, w)), expect)


def test_sliced_padded_cells_bounded(g_skewed):
    """Bucketing must keep padded slots near O(E), far under N·max_deg."""
    g = g_skewed
    ell = prepare_sliced_ell(g, reverse=True)
    dense_cells = g.num_nodes * max(g.max_in_degree, 1)
    assert ell.padded_cells() <= 0.25 * dense_cells
    assert ell.padded_cells() >= g.num_edges - ell.hub_cols.shape[0]


# --- tc_matmul ----------------------------------------------------------------

@pytest.mark.parametrize("n,block", [(64, 32), (128, 64), (128, 128)])
def test_tc_matmul_sweep(n, block):
    rng = np.random.default_rng(n)
    a = (rng.random((n, n)) < 0.1).astype(np.float32)
    lower = jnp.asarray(np.tril(a, -1))
    got = float(tc_matmul(lower, block=block))
    ref = float(tc_matmul_ref(lower))
    assert got == ref


def test_tc_dense_vs_networkx(g_social):
    import networkx as nx
    lower = prepare_lower(g_social, block=64)
    got = int(count_triangles_dense(lower, block=64))
    G = nx.Graph()
    G.add_edges_from(zip(np.asarray(g_social.edge_src).tolist(),
                         np.asarray(g_social.indices).tolist()))
    assert got == sum(nx.triangles(G).values()) // 3


# --- flash attention -------------------------------------------------------------

@pytest.mark.parametrize("bh,sq,skv,d", [
    (2, 128, 128, 64), (1, 256, 256, 32), (3, 128, 256, 64), (2, 64, 512, 128),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(bh, sq, skv, d, causal):
    rng = np.random.default_rng(bh * sq)
    q = jnp.asarray(rng.normal(size=(bh, sq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(bh, skv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(bh, skv, d)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)
    assert got.dtype == jnp.bfloat16


def test_gqa_grouping():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 8, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 128, 64)), jnp.float32)
    o_k = gqa_attention(q, k, v, use_kernel=True)
    o_r = gqa_attention(q, k, v, use_kernel=False)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-5)
