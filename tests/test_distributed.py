"""Distributed backend vs the NumPy oracles — in-process on the 8 forced
host devices (see conftest.py), under the default dense-gather schedule.
The frontier-compressed exchange policies are covered by
test_dist_agree.py / test_dist_padding.py / test_property.py; this module
pins the paper-faithful baseline plus the beyond-paper 2-D and
pod-parallel paths.
"""
import numpy as np
import pytest

from repro.core import compile_bundled, dist
from repro.core.dist2d import pagerank_2d, sssp_2d
from repro.graph import road, uniform_random
from repro.graph.algorithms_ref import (bc_ref, pagerank_ref, sssp_ref,
                                        triangle_count_ref)


@pytest.fixture(scope="module")
def g(eight_devices):
    return uniform_random(100, 5, seed=2)


@pytest.fixture(scope="module")
def mesh8(eight_devices):
    return dist.make_mesh_1d(8)


def test_sssp_1d(g, mesh8):
    p = compile_bundled("sssp", backend="distributed")
    out = dist.run(p, g, mesh8, src=0)
    assert np.array_equal(np.asarray(out["dist"]),
                          sssp_ref(g, 0).astype(np.int32))


def test_sssp_pull_1d(g, mesh8):
    p = compile_bundled("sssp_pull", backend="distributed")
    out = dist.run(p, g, mesh8, src=0)
    assert np.array_equal(np.asarray(out["dist"]),
                          sssp_ref(g, 0).astype(np.int32))


def test_pr_1d(g, mesh8):
    p = compile_bundled("pr", backend="distributed")
    out = dist.run(p, g, mesh8, beta=1e-4, delta=0.85, maxIter=60)
    assert np.allclose(np.asarray(out["pageRank"]), pagerank_ref(g),
                       atol=1e-5)


def test_tc_1d(g, mesh8):
    p = compile_bundled("tc", backend="distributed")
    assert int(dist.run(p, g, mesh8)["triangle_count"]) == triangle_count_ref(g)


def test_bc_1d(g, mesh8):
    p = compile_bundled("bc", backend="distributed")
    srcs = np.array([0, 7, 23], np.int32)
    out = dist.run(p, g, mesh8, sourceSet=srcs)
    assert np.allclose(np.asarray(out["BC"]), bc_ref(g, [0, 7, 23]),
                       atol=1e-3)


def test_sssp_1d_road(mesh8):
    gr = road(10, seed=3)     # large diameter — many BSP supersteps
    p = compile_bundled("sssp", backend="distributed")
    out = dist.run(p, gr, mesh8, src=0)
    assert np.array_equal(np.asarray(out["dist"]),
                          sssp_ref(gr, 0).astype(np.int32))


def test_sssp_2d(g, eight_devices):
    mesh2 = dist.make_mesh((4, 2), ("data", "model"))
    assert np.array_equal(np.asarray(sssp_2d(g, mesh2, 0)),
                          sssp_ref(g, 0).astype(np.int32))


def test_pr_2d(g, eight_devices):
    mesh2 = dist.make_mesh((4, 2), ("data", "model"))
    assert np.allclose(np.asarray(pagerank_2d(g, mesh2)), pagerank_ref(g),
                       atol=1e-5)


def test_bc_pod_parallel(g, eight_devices):
    mesh3 = dist.make_mesh((2, 4), ("pod", "data"))
    p = compile_bundled("bc", backend="distributed")
    srcs4 = np.array([0, 7, 23, 41], np.int32)
    out = dist.run_pod_parallel(p, g, mesh3, srcs4)
    assert np.allclose(np.asarray(out["BC"]), bc_ref(g, srcs4.tolist()),
                       atol=1e-3)
    # the communication counter is psum'd across pods: it must equal the
    # sum of the two per-pod (4-shard) runs, not one arbitrary pod's count
    mesh4 = dist.make_mesh_1d(4)
    per_pod = sum(
        float(p.bind(g, mesh=mesh4)(sourceSet=s)["_gather_elems"])
        for s in (srcs4[:2], srcs4[2:]))
    assert float(out["_gather_elems"]) == per_pod
