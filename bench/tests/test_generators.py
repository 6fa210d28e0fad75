"""The benchmark's generators at small sizes: the Graph500 quadrant
probabilities, the counts, determinism, and that every seed gives the same
graph up to a relabelling."""
import numpy as np
import pytest

import edgelist
import harness
from seeds import pair_weights

KRON = dict(scale=12, edgefactor=16, A=0.57, B=0.19, C=0.19, weight_lo=1,
            weight_hi=2**20 - 1, graph_seed=20)


def gen(name):
    return harness.load_module("graphs", name).generate


def base_edges(e):
    """The edge list in base labels (the run's labelling undone)."""
    inv = np.argsort(e["label"])
    return inv[e["src"]], inv[e["dst"]], e["w"]


def test_kronecker_quadrant_probabilities():
    e = gen("kronecker")(dict(KRON, scale=1, edgefactor=40000), seed=3)
    i, j, _ = base_edges(e)
    freq = np.bincount(2 * i + j, minlength=4) / len(i)
    assert freq == pytest.approx([0.57, 0.19, 0.19, 0.05], abs=0.01)


def test_kronecker_counts_at_scale_12():
    """Graph500's rules (undirected, self-loops and duplicates dropped) at
    scale 12, from graph_seed 20. At scale 20 the same generator gives
    about 31.4M directed entries, 62% of vertices with an edge and a hub of
    degree about 65k; the shares move toward those as the scale grows."""
    shares = []
    for scale in (10, 12, 14):
        e = gen("kronecker")(dict(KRON, scale=scale), seed=5)
        adj, _ = edgelist.matrices(e, weighted=False)
        deg = np.diff(adj.indptr)
        shares.append((adj.nnz / (2 * 16 << scale), (deg > 0).mean(), deg.max()))
        if scale == 12:
            assert (e["n"], len(e["src"])) == (4096, 65536)
            assert (adj.nnz, int((deg > 0).sum()), int(deg.max())) == (97346, 3322, 1355)
    kept, with_edge, hub = zip(*shares)
    assert kept[0] < kept[1] < kept[2] < 0.936 and with_edge[0] > with_edge[1] > with_edge[2] > 0.616
    assert hub[0] < hub[1] < hub[2] < 64701


def test_every_seed_gives_the_same_graph_relabelled():
    a, b = gen("kronecker")(KRON, seed=2**31 + 1), gen("kronecker")(KRON, seed=2**31 + 6)
    again = gen("kronecker")(KRON, seed=2**31 + 1)
    assert all(np.array_equal(a[k], again[k]) for k in ("src", "dst", "w", "label"))
    assert not np.array_equal(a["src"], b["src"])
    for x, y in zip(base_edges(a), base_edges(b)):
        assert np.array_equal(x, y)
    assert sorted(a["label"]) == list(range(a["n"]))


def test_pair_weights_are_symmetric_and_in_range():
    rng = np.random.default_rng(0)
    u, v = rng.integers(0, 1 << 20, size=(2, 100000))
    w = pair_weights(u, v, 1, 100, 20)
    assert np.array_equal(w, pair_weights(v, u, 1, 100, 20))
    assert w.min() == 1 and w.max() == 100
    assert np.bincount(w)[1:].min() > 800          # about 1,000 each


def test_pair_weights_span_twenty_bits():
    rng = np.random.default_rng(1)
    u, v = rng.integers(0, 1 << 21, size=(2, 100000))
    w = np.asarray(pair_weights(u, v, 1, 2**20 - 1, 20))
    assert w.min() >= 1 and w.max() <= 2**20 - 1
    assert np.histogram(w, bins=4, range=(0, 2**20))[0].min() > 24000   # uniform
