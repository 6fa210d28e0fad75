"""The graph's host views and the device views built from them.

`from_edges` builds the CSR on the host with one packed-key sort per
direction, and the 1-D partition is slices of it; the references below
are the builds they replaced (`np.unique` plus two `np.lexsort`, and the
per-device mask loop of the 1-D partition), kept here to hold the new
ones to the same arrays bit for bit. The device views: one whole copy per graph on one device for the
local backend, and on the distributed backend each device's own shard
only, placed straight from the host arrays.
"""
import jax
import numpy as np
import pytest

from repro.core import compile_bundled, dist, get_context, prepare
from repro.core import runtime_dist as rtd
from repro.graph import csr, from_edges
from repro.graph.algorithms_ref import pagerank_ref
from repro.graph.csr import INF_I32

PR = dict(beta=1e-6, delta=0.85, maxIter=100)


# -- the builds the new code replaced ------------------------------------------

def _lexsort_csr(n, src, dst, w):
    order = np.lexsort((dst, src))
    src, dst, w = src[order], dst[order], w[order]
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, src + 1, 1)
    indptr = np.cumsum(indptr)
    return (indptr.astype(np.int32), dst.astype(np.int32), w.astype(np.int32),
            src.astype(np.int32))


def reference_from_edges(n, src, dst, weights=None, *, undirected=False,
                         dedup=True, drop_self_loops=False) -> dict:
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.ones_like(src) if weights is None else np.asarray(weights, np.int64)
    if undirected:
        src, dst, w = (np.concatenate([src, dst]), np.concatenate([dst, src]),
                       np.concatenate([w, w]))
    if drop_self_loops:
        keep = src != dst
        src, dst, w = src[keep], dst[keep], w[keep]
    if dedup and len(src):
        _, first = np.unique(src * np.int64(n) + dst, return_index=True)
        src, dst, w = src[first], dst[first], w[first]
    indptr, indices, w_s, edge_src = _lexsort_csr(n, src, dst, w)
    rev_indptr, rev_indices, rev_w, rev_edge_dst = _lexsort_csr(n, dst, src, w)
    out_deg = np.diff(indptr).astype(np.int32)
    in_deg = np.diff(rev_indptr).astype(np.int32)
    return dict(
        indptr=indptr, indices=indices, weights=w_s, edge_src=edge_src,
        rev_indptr=rev_indptr, rev_indices=rev_indices, rev_weights=rev_w,
        rev_edge_dst=rev_edge_dst, out_degree=out_deg, in_degree=in_deg,
        edge_key=(edge_src.astype(np.int64) * n + indices).astype(np.int32),
        num_edges=len(src), max_out_degree=int(out_deg.max(initial=1)),
        max_in_degree=int(in_deg.max(initial=1)))


def reference_partition(g, p, reverse=False):
    """The per-device boolean-mask partition over all E."""
    block = -(-g.num_nodes // p)
    if reverse:
        src, dst, w = g.rev_edge_dst, g.rev_indices, g.rev_weights
    else:
        src, dst, w = g.edge_src, g.indices, g.weights
    src, dst, w = np.asarray(src), np.asarray(dst), np.asarray(w)
    owner = src // block
    emax = max(int(np.bincount(owner, minlength=p).max()) if len(src) else 0, 1)
    out = [np.zeros((p, emax), np.int32), np.zeros((p, emax), np.int32),
           np.full((p, emax), int(INF_I32), np.int32), np.zeros((p, emax), bool)]
    for d in range(p):
        sel = owner == d
        k = int(sel.sum())
        for arr, val in zip(out, (src[sel], dst[sel], w[sel], True)):
            arr[d, :k] = val
    return out, block


# -- edge lists ----------------------------------------------------------------

def kronecker_edges(scale, edgefactor=8, seed=0):
    """Graph500-style Kronecker edges with their duplicates and self-loops,
    and weights that differ between the copies of an edge."""
    rng = np.random.default_rng(seed)
    m = edgefactor << scale
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        r = rng.random(m)
        src |= (r >= 0.76).astype(np.int64) << bit
        dst |= ((r >= 0.57) & (r < 0.76) | (r >= 0.95)).astype(np.int64) << bit
    return 1 << scale, src, dst, rng.integers(1, 1 << 20, m)


def hand_made():
    """Duplicates with different weights, both directions, self-loops, a
    triangle (0, 1, 2), and isolated vertices 4, 7 and 8."""
    src = np.array([0, 0, 1, 2, 2, 3, 3, 5, 6, 0, 1, 9, 2])
    dst = np.array([1, 1, 0, 2, 3, 2, 5, 6, 5, 1, 2, 9, 0])
    w = np.array([5, 7, 3, 1, 2, 9, 4, 4, 8, 6, 2, 1, 3])
    return 10, src, dst, w


EDGE_LISTS = {
    "kron8": kronecker_edges(8, seed=1),
    "kron11": kronecker_edges(11, seed=2),
    "hand": hand_made(),
    "empty": (5, np.zeros(0, np.int64), np.zeros(0, np.int64), None),
}
BUILDS = [dict(undirected=u, dedup=d, drop_self_loops=s)
          for u in (False, True) for d in (True, False) for s in (False, True)]


def _build_id(kw):
    return "-".join(k for k, v in kw.items() if v) or "directed"


@pytest.mark.parametrize("kw", BUILDS, ids=_build_id)
@pytest.mark.parametrize("name", sorted(EDGE_LISTS))
@pytest.mark.parametrize("parallel", [False, True], ids=["one_piece", "pieces"])
def test_from_edges_matches_the_lexsort_build(name, kw, parallel, monkeypatch):
    if parallel:      # the threaded path at test size: many pieces, buckets
        monkeypatch.setattr(csr, "_PARALLEL_MIN_EDGES", 16)
        monkeypatch.setattr(csr, "_workers", lambda: 5)
        monkeypatch.setattr(csr, "_PIECE", 64)
    n, src, dst, w = EDGE_LISTS[name]
    g = from_edges(n, src, dst, w, **kw)
    want = reference_from_edges(n, src, dst, w, **kw)
    for key, val in want.items():
        got = getattr(g, key)
        if isinstance(val, np.ndarray):
            assert isinstance(got, np.ndarray) and got.dtype == val.dtype, key
            assert np.array_equal(got, val), key
        else:
            assert got == val, key


def test_from_edges_rejects_ids_outside_the_graph():
    with pytest.raises(ValueError, match="outside"):
        from_edges(4, [0, 4], [1, 2])
    with pytest.raises(ValueError, match="outside"):
        from_edges(4, [0, 1], [-1, 2])


# -- the 1-D partition ---------------------------------------------------------

# name -> (edge list, undirected); self-loops are kept where directed
PARTITION_GRAPHS = {"kron8_und": ("kron8", True), "kron11_dir": ("kron11", False),
                    "hand_dir": ("hand", False), "hand_und": ("hand", True)}


def _graph(name):
    edges, undirected = PARTITION_GRAPHS[name]
    n, src, dst, w = EDGE_LISTS[edges]
    return from_edges(n, src, dst, w, undirected=undirected,
                      drop_self_loops=undirected)


# the partitioned arrays of each direction, by the mask loop's field
DIRECTIONS = {
    "out": {"esrc": 0, "edst": 1, "ew": 2, "evalid": 3, "esrc_local": 0},
    "in": {"idst": 0, "isrc": 1, "iw": 2, "ivalid": 3, "idst_local": 0},
}


@pytest.mark.parametrize("direction", sorted(DIRECTIONS))
@pytest.mark.parametrize("p", [1, 3, 4, 8])
@pytest.mark.parametrize("name", sorted(PARTITION_GRAPHS))
def test_placed_shards_are_the_partition(eight_devices, name, p, direction):
    """Row d of every partitioned array is device d's shard, and equals the
    mask loop's row d; `esrc_local` / `idst_local` are the owned
    endpoint's local slots."""
    g = _graph(name)
    gd = rtd.prepare_graph_1d(g, dist.make_mesh_1d(p))
    fields, block = reference_partition(g, p, reverse=direction == "in")
    slots = np.clip(fields[0] - (np.arange(p) * block)[:, None], 0,
                    block - 1).astype(np.int32)
    want = {key: slots if key.endswith("_local") else fields[i]
            for key, i in DIRECTIONS[direction].items()}
    want["own_ids"] = np.arange(p * block, dtype=np.int32).reshape(p, block)
    for key, val in want.items():
        arr = gd[key]
        assert arr.shape == val.shape and arr.dtype == val.dtype, key
        assert np.array_equal(np.asarray(arr), val), key
        for shard in arr.addressable_shards:
            d = shard.index[0].start or 0
            assert np.array_equal(np.asarray(shard.data)[0], val[d]), key
    assert "edge_key_rep" not in gd


# -- device views --------------------------------------------------------------

def test_distributed_prepare_puts_no_whole_edge_array_on_one_device(eight_devices):
    n, src, dst, w = kronecker_edges(12, seed=4)
    g = from_edges(n, src, dst, w, undirected=True, drop_self_loops=True)
    prog = compile_bundled("pr", backend="distributed")
    mesh = dist.make_mesh_1d(4)
    before = {id(a) for a in jax.live_arrays()}
    prepare(g, program=prog, mesh=mesh)
    new = [a for a in jax.live_arrays() if id(a) not in before]
    assert new
    whole = [a.shape for a in new
             if len(a.sharding.device_set) == 1 and a.size >= g.num_edges]
    assert whole == []
    per = {}
    for a in new:
        for s in a.addressable_shards:
            per[s.device] = per.get(s.device, 0) + s.data.nbytes
    assert len(per) == 4 and max(per.values()) <= 1.15 * min(per.values())
    assert isinstance(g.indices, np.ndarray)          # the host view stays
    assert ("device",) not in get_context(g).view_keys()


def test_local_bind_keeps_one_device_copy_per_graph():
    n, src, dst, w = kronecker_edges(9, seed=5)
    g = from_edges(n, src, dst, w, undirected=True)
    prog = compile_bundled("pr")
    ctx = prepare(g, program=prog)
    dev = ctx.device_graph()
    assert all(isinstance(getattr(dev, k), jax.Array)
               for k in ("indptr", "indices", "rev_indices", "edge_key"))
    assert isinstance(g.indices, np.ndarray)
    first = prog.bind(g)
    out = first(**PR)
    del first
    second = prog.bind(g)
    assert get_context(g).device_graph() is dev
    assert [k for k in ctx.view_keys() if k[0] == "device"] == [("device",)]
    assert np.array_equal(np.asarray(second(**PR)["pageRank"]),
                          np.asarray(out["pageRank"]))
    assert np.array_equal(np.asarray(prog(g, **PR)["pageRank"]),
                          np.asarray(out["pageRank"]))


@pytest.mark.parametrize("p", [2, 4, 8])
@pytest.mark.parametrize("name", ["kron11", "hand"])
def test_distributed_pr_agrees_with_local_and_reference(eight_devices, name, p):
    n, src, dst, w = EDGE_LISTS[name]
    g = from_edges(n, src, dst, w, undirected=True, drop_self_loops=True)
    prog = compile_bundled("pr", backend="distributed")
    mesh = dist.make_mesh_1d(p)
    prepare(g, program=prog, mesh=mesh)
    got = prog.bind(g, mesh=mesh)(**PR)
    local = compile_bundled("pr").bind(g)(**PR)
    ref = pagerank_ref(g, PR["delta"], PR["beta"], PR["maxIter"])
    rank = np.asarray(got["pageRank"])
    np.testing.assert_allclose(rank, np.asarray(local["pageRank"]), atol=1e-6)
    np.testing.assert_allclose(rank, ref, atol=1e-5)
    assert int(got["iterCount"]) == int(local["iterCount"])


@pytest.mark.parametrize("name,params", [("pr", PR), ("sssp", dict(src=0)),
                                         ("cc", {})])
def test_distributed_supersteps_equal_local(eight_devices, name, params):
    n, src, dst, w = EDGE_LISTS["kron11"]
    g = from_edges(n, src, dst, w, undirected=True, drop_self_loops=True)
    got = compile_bundled(name, backend="distributed").bind(
        g, mesh=dist.make_mesh_1d(4))(**params)
    local = compile_bundled(name).bind(g)(**params)
    assert int(got["_supersteps"]) == int(local["_supersteps"]) > 0


def test_distributed_tc_edge_key_only_where_it_fits(eight_devices):
    """The replicated edge key goes to the devices only for a program that
    reads it, and only while src*N + dst fits int32; past N = 46,340 the
    distributed triangle count refuses instead of counting wrong."""
    mesh = dist.make_mesh_1d(4)
    tc = compile_bundled("tc", backend="distributed")
    assert tc.dist_meta["needs_edge_key"]
    assert not compile_bundled("pr", backend="distributed").dist_meta["needs_edge_key"]
    n, src, dst, w = EDGE_LISTS["hand"]
    small = from_edges(n, src, dst, w, undirected=True, drop_self_loops=True)
    got = tc.bind(small, mesh=mesh)()
    want = compile_bundled("tc").bind(small)()
    assert int(got["triangle_count"]) == int(want["triangle_count"]) > 0
    n = 46_341
    ring = np.arange(n)
    big = from_edges(n, ring, (ring + 1) % n, undirected=True)
    with pytest.raises(ValueError, match="Design 2"):
        tc.bind(big, mesh=mesh)
