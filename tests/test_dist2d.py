"""2-D adjacency-partitioned kernels (core/dist2d) vs the NumPy oracles.

The 2-D path blocks the adjacency over an R x C grid and moves O(N/C)
bytes per collective instead of the 1-D backend's O(N); correctness must
not depend on the grid shape, on N dividing the device count, or on the
graph's diameter. This module sweeps those axes on the 8 forced host
devices (see conftest.py); test_distributed.py keeps the one-shape smoke
next to the 1-D agreement tests.
"""
import numpy as np
import pytest

from repro.core import dist
from repro.core.dist2d import pagerank_2d, sssp_2d
from repro.graph import road, uniform_random
from repro.graph.algorithms_ref import pagerank_ref, sssp_ref

# grid shapes with 8, 4, and 2 devices: column-count c (the collective
# divisor) varies from 1 to 4, and the single-row / single-column edges
# degenerate toward 1-D partitioning in each direction
MESHES = [(4, 2), (2, 4), (2, 2), (8, 1), (1, 8), (2, 1), (1, 2)]


def _mesh(r, c):
    return dist.make_mesh((r, c), ("data", "model"))


@pytest.fixture(scope="module")
def g(eight_devices):
    # N=100 never divides 8 evenly -> every shape exercises piece padding
    return uniform_random(100, 5, seed=2)


@pytest.fixture(scope="module")
def local_refs(g):
    return {"sssp0": sssp_ref(g, 0).astype(np.int32),
            "sssp17": sssp_ref(g, 17).astype(np.int32),
            "pr": pagerank_ref(g)}


@pytest.mark.parametrize("r,c", MESHES)
def test_sssp_2d_agrees(g, local_refs, r, c):
    assert np.array_equal(np.asarray(sssp_2d(g, _mesh(r, c), 0)),
                          local_refs["sssp0"])


@pytest.mark.parametrize("r,c", [(4, 2), (1, 8)])
def test_sssp_2d_nonzero_source(g, local_refs, r, c):
    assert np.array_equal(np.asarray(sssp_2d(g, _mesh(r, c), 17)),
                          local_refs["sssp17"])


@pytest.mark.parametrize("r,c", MESHES)
def test_pagerank_2d_agrees(g, local_refs, r, c):
    assert np.allclose(np.asarray(pagerank_2d(g, _mesh(r, c))),
                       local_refs["pr"], atol=1e-5)


def test_sssp_2d_deep_graph(eight_devices):
    # high-diameter road grid: many BSP supersteps through the while_loop
    gr = road(10, seed=3)
    assert np.array_equal(np.asarray(sssp_2d(gr, _mesh(2, 4), 0)),
                          sssp_ref(gr, 0).astype(np.int32))


def test_pagerank_2d_respects_maxiter(g, eight_devices):
    # one sweep from the uniform init is the damped one-step power iterate;
    # the 2-D path must honor max_iter exactly, not just convergence
    one = np.asarray(pagerank_2d(g, _mesh(2, 2), max_iter=1))
    ref = pagerank_ref(g, max_iter=1)
    assert np.allclose(one, ref, atol=1e-6)
    assert not np.allclose(one, pagerank_ref(g), atol=1e-5)
