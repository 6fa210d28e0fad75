"""The benchmark's plain references against the repository's own oracles
(`repro.graph.algorithms_ref`) on small graphs, and the lower-precision
store that the control uses."""
import ml_dtypes
import numpy as np
import pytest

import harness
from repro.graph import algorithms_ref, from_edges

CASES = [("kronecker", dict(scale=7, edgefactor=8, A=0.57, B=0.19, C=0.19, weight_lo=1,
                            weight_hi=2**20 - 1, graph_seed=3)),
         ("kronecker", dict(scale=8, edgefactor=4, A=0.57, B=0.19, C=0.19, weight_lo=1,
                            weight_hi=100, graph_seed=4))]


def graph_and_edges(name, params):
    e = harness.load_module("graphs", name).generate(params, seed=2**31 + 3)
    g = from_edges(e["n"], e["src"], e["dst"], e["w"], undirected=e["undirected"],
                   drop_self_loops=e["drop_self_loops"])
    return g, e


@pytest.mark.parametrize("name,params", CASES)
def test_pagerank_matches_the_oracle(name, params):
    g, e = graph_and_edges(name, params)
    ref = harness.load_module("references", "pr")
    got = ref.pagerank(e, 0.85, 1e-4, 100)
    want = algorithms_ref.pagerank_ref(g, 0.85, 1e-4, 100)
    assert got["num_edges"] == g.num_edges
    assert np.abs(got["rank"] - want).max() < 1e-12


@pytest.mark.parametrize("name,params", CASES)
def test_sssp_matches_the_oracle(name, params):
    g, e = graph_and_edges(name, params)
    ref = harness.load_module("references", "sssp")
    roots = [int(np.argmax(np.asarray(g.out_degree))), 0, g.num_nodes - 1]
    got = ref.distances(e, roots)
    assert got["num_edges"] == g.num_edges
    for root, dist in zip(roots, got["dist"]):
        want = algorithms_ref.sssp_ref(g, root).astype(np.float64)
        want[want >= 2**30] = np.inf
        assert np.array_equal(dist, want)


def test_bfloat16_store_departs_from_float64():
    _, e = graph_and_edges(*CASES[0])
    ref = harness.load_module("references", "pr")
    exact = ref.pagerank(e, 0.85, 1e-4, 100)["rank"]
    low = ref.pagerank(e, 0.85, 1e-4, 100, store=ml_dtypes.bfloat16)["rank"]
    assert 1e-4 < np.abs(low - exact).sum() < 0.1
